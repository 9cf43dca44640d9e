"""Device time by the program's named scopes, read from the profiler trace.

The program names its phases with `jax.named_scope`; the compiler keeps
each as its instructions' `op_name` metadata, e.g. `jit(cb_eval)/
geostat_loglik_step/factor/potrf/jit(cholesky)/cholesky`.  The scope
path is what is left once transforms (`jit(...)`, `vmap(...)`) and the
primitive that ends the name are dropped.

The trace carries what is needed: the plane `/host:metadata` holds the
compiled HLO of every program traced (an `HloProto` per program id),
and each device op's event metadata names its instruction and holds its
`program_id`.  JAX's `ProfileData` shows neither, so this module reads
the `.xplane.pb` with a schema of its own: the few fields of XSpace and
of the HLO protos it needs, wire-compatible with the profiler's and
XLA's `.proto` files (a map is a repeated key/value message on the
wire); the rest is skipped.

A fusion takes its fused root's scope, else its own.  An instruction the
compiler made without an `op_name` (a layout copy, a loop that writes a
solve's result back) takes, in a loop's body or a conditional's branch,
the scope of that loop or conditional, and otherwise the common scope of
the scoped instructions it reads and feeds: a copy between
`factor/trsm_hi` and `factor/update_hi` counts under `factor`, in no
phase.  What is left counts under `other`.

Device seconds are each op's self time (a `while` op's event holds its
body's ops, each an event too), clipped to the host span `cb.window`.
A program built without the scopes has none of their paths, and a
reader of them finds nothing.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from pathlib import Path

from chipbench import trace

NO_SCOPE = "other"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
PS = 1e-12
U64 = (1 << 64) - 1

_MESSAGES = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, "string", False),
               ("lines", 3, "XLine", True),
               ("event_metadata", 4, "EventMetadataEntry", True),
               ("stat_metadata", 5, "StatMetadataEntry", True)],
    "EventMetadataEntry": [("key", 1, "int64", False),
                           ("value", 2, "XEventMetadata", False)],
    "StatMetadataEntry": [("key", 1, "int64", False),
                          ("value", 2, "XStatMetadata", False)],
    "XLine": [("name", 2, "string", False),
              ("timestamp_ns", 3, "int64", False),
              ("events", 4, "XEvent", True)],
    "XEvent": [("metadata_id", 1, "int64", False),
               ("offset_ps", 2, "int64", False),
               ("duration_ps", 3, "int64", False)],
    "XStat": [("metadata_id", 1, "int64", False),
              ("uint64_value", 3, "uint64", False),
              ("int64_value", 4, "int64", False),
              ("bytes_value", 6, "bytes", False)],
    "XEventMetadata": [("name", 2, "string", False),
                       ("stats", 5, "XStat", True)],
    "XStatMetadata": [("name", 2, "string", False)],
    "HloProto": [("hlo_module", 1, "HloModuleProto", False)],
    "HloModuleProto": [("name", 1, "string", False),
                       ("computations", 3, "HloComputationProto", True)],
    "HloComputationProto": [("name", 1, "string", False),
                            ("instructions", 2, "HloInstructionProto", True),
                            ("id", 5, "int64", False),
                            ("root_id", 6, "int64", False)],
    "HloInstructionProto": [("name", 1, "string", False),
                            ("opcode", 2, "string", False),
                            ("metadata", 7, "OpMetadata", False),
                            ("id", 35, "int64", False),
                            ("operand_ids", 36, "int64", True),
                            ("called_computation_ids", 38, "int64", True)],
    "OpMetadata": [("op_name", 2, "string", False)],
}


@functools.cache
def schema() -> dict:
    """Message classes for the fields of XSpace and HloProto read here."""
    from google.protobuf import descriptor_pb2, descriptor_pool, \
        message_factory

    F = descriptor_pb2.FieldDescriptorProto
    types = {"int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64,
             "string": F.TYPE_STRING, "bytes": F.TYPE_BYTES}
    fdp = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench_xplane",
        syntax="proto3")
    for mname, fields in _MESSAGES.items():
        msg = fdp.message_type.add(name=mname)
        for fname, number, ftype, repeated in fields:
            f = msg.field.add(name=fname, number=number,
                              label=F.LABEL_REPEATED if repeated
                              else F.LABEL_OPTIONAL)
            if ftype in types:
                f.type = types[ftype]
            else:
                f.type = F.TYPE_MESSAGE
                f.type_name = f".chipbench_xplane.{ftype}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return {name: message_factory.GetMessageClass(
        pool.FindMessageTypeByName(f"chipbench_xplane.{name}"))
        for name in _MESSAGES}


def _components(op_name: str) -> list:
    """Split an `op_name` at the `/` outside parentheses."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(op_name):
        depth += (ch == "(") - (ch == ")")
        if ch == "/" and depth == 0:
            out.append(op_name[start:i])
            start = i + 1
    out.append(op_name[start:])
    return out


def scope_path(op_name: str) -> str:
    """The named-scope path of an `op_name`: its components without the
    primitive that ends it and without transforms (`jit(...)`,
    `vmap(...)`); `other` where nothing is left.  Of merged names
    (`a;b`) the first counts."""
    parts = _components(op_name.split(";")[0])
    if parts and "(" not in parts[-1]:
        parts = parts[:-1]               # the primitive, e.g. `dot_general`
    path = "/".join(p for p in parts if p and "(" not in p)
    return path or NO_SCOPE


def _common(paths) -> str:
    """The longest common scope of paths: `a/b/c`, `a/b/d` -> `a/b`."""
    out = []
    for parts in zip(*(p.split("/") for p in paths)):
        if len(set(parts)) > 1:
            break
        out.append(parts[0])
    return "/".join(out) or NO_SCOPE


def scope_map(module) -> dict:
    """{instruction name: scope path} of a compiled `HloModuleProto`."""
    ins, comp_of, roots = {}, {}, {}
    for c in module.computations:
        roots[c.id] = c.root_id
        for i in c.instructions:
            ins[i.id] = i
            comp_of[i.id] = c.id
    inner, caller = set(), {}    # fused and reducer computations; the loop
    for i in ins.values():       # or conditional that runs a computation
        for c in i.called_computation_ids:
            if i.opcode in ("while", "conditional"):
                caller[c] = i.id
            else:
                inner.add(c)

    def op_name(n, depth=0):
        i = ins[n]
        if i.opcode == "fusion" and depth < 8:
            for c in i.called_computation_ids:
                root = roots.get(c)
                if root in ins:
                    return op_name(root, depth + 1) or i.metadata.op_name
        return i.metadata.op_name

    names = {n: op_name(n) for n in ins}
    scope = {n: scope_path(v) for n, v in names.items()}
    feeds: dict = {}
    for n, i in ins.items():
        for r in i.operand_ids:
            if comp_of.get(r) == comp_of[n]:
                feeds.setdefault(r, []).append(n)
    unscoped = [n for n in ins if comp_of[n] not in inner and not names[n]]
    for _ in range(8):                   # chains of unscoped instructions
        changed = False
        for n in unscoped:
            if scope[n] != NO_SCOPE:
                continue
            up = caller.get(comp_of[n])
            if up is not None and scope[up] != NO_SCOPE:
                near = [scope[up]]
            else:
                near = [scope[x] for x in list(ins[n].operand_ids)
                        + feeds.get(n, [])
                        if comp_of.get(x) == comp_of[n]
                        and scope[x] != NO_SCOPE]
            if near:
                scope[n] = _common(near)
                changed = changed or scope[n] != NO_SCOPE
        if not changed:
            break
    return {ins[n].name: s for n, s in scope.items()}


@dataclass
class Scopes:
    window_s: float
    # program (`cb_eval` for `jit_cb_eval`) -> {scope path -> device
    # seconds}; ops of a program whose HLO the trace lacks under None
    seconds: dict = field(default_factory=dict)

    def total(self, name: str, *paths: str) -> float | None:
        """Device seconds of program `name` under the paths given, each
        with what nests inside it; None where no op is under any."""
        found = [s for k, s in self.seconds.get(name, {}).items()
                 if any(k == p or k.startswith(p + "/") for p in paths)]
        return sum(found) if found else None

    def share(self, path: str = NO_SCOPE) -> float:
        """Share of all device seconds under `path`, in any program."""
        busy = sum(s for d in self.seconds.values() for s in d.values())
        got = sum(d.get(path, 0.0) for d in self.seconds.values())
        return got / busy if busy else 0.0


def _self_ps(events):
    """Self time of [(start, end, key)]: an event that holds others (a
    loop's, around its body's) loses what they cover."""
    events = sorted(events, key=lambda ev: (ev[0], -ev[1]))
    own = [e - s for s, e, _ in events]
    stack: list = []
    for i, (s, e, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(key, t) for (_, _, key), t in zip(events, own)]


def _programs(plane) -> dict:
    """{program id: (program name, scope map)} from `/host:metadata`."""
    hlo = schema()["HloProto"]
    stat_names = {m.key: m.value.name for m in plane.stat_metadata}
    out = {}
    for m in plane.event_metadata:
        for st in m.value.stats:
            if stat_names.get(st.metadata_id) == HLO_STAT:
                proto = hlo()
                proto.ParseFromString(st.bytes_value)
                name = proto.hlo_module.name
                out[m.key & U64] = (name.removeprefix("jit_"),
                                    scope_map(proto.hlo_module))
    return out


def reduce_space(space) -> Scopes:
    """Device seconds per program and scope path in an XSpace message."""
    window = None
    devices, programs = [], {}
    for plane in space.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            devices.append(plane)
        elif plane.name == METADATA_PLANE:
            programs.update(_programs(plane))
        elif plane.name.startswith("/host") and window is None:
            names = {m.key: m.value.name for m in plane.event_metadata}
            for line in plane.lines:
                for ev in line.events:
                    if names.get(ev.metadata_id) == trace.WINDOW_SPAN:
                        w0 = line.timestamp_ns * 1000 + ev.offset_ps
                        window = (w0, w0 + ev.duration_ps)
                        break
    if window is None:
        raise ValueError(f"trace has no host span {trace.WINDOW_SPAN!r}")
    w0, w1 = window
    seconds: dict = {}
    for plane in devices:
        stat_names = {m.key: m.value.name for m in plane.stat_metadata}
        where = {}
        for m in plane.event_metadata:
            pid = next((st.uint64_value or st.int64_value
                        for st in m.value.stats
                        if stat_names.get(st.metadata_id) == "program_id"),
                       None)
            name, scopes = programs.get(pid & U64 if pid is not None
                                        else None, (None, {}))
            instruction = m.value.name.split(" ")[0].lstrip("%")
            where[m.key] = (name, scopes.get(instruction, NO_SCOPE))
        for line in plane.lines:
            if line.name != trace.OPS_LINE:
                continue
            t0 = line.timestamp_ns * 1000
            clipped = []
            for ev in line.events:
                s = t0 + ev.offset_ps
                e = s + ev.duration_ps
                if s < w1 and e > w0:
                    clipped.append((max(s, w0), min(e, w1),
                                    where.get(ev.metadata_id,
                                              (None, NO_SCOPE))))
            for (name, path), t in _self_ps(clipped):
                d = seconds.setdefault(name, {})
                d[path] = d.get(path, 0.0) + t * PS / len(devices)
    return Scopes(window_s=(w1 - w0) * PS, seconds=seconds)


@functools.lru_cache(maxsize=4)
def _reduce(path: str, stamp) -> Scopes:
    space = schema()["XSpace"]()
    space.ParseFromString(Path(path).read_bytes())
    return reduce_space(space)


def reduce(path: str | Path) -> Scopes:
    """`reduce_space` of an `.xplane.pb`, read once per file."""
    st = Path(path).stat()
    return _reduce(str(path), (st.st_mtime_ns, st.st_size))


def traced_file() -> Path:
    """The trace that a `--trace 1` run has just written."""
    from chipbench.run import TRACE_DIR
    return trace.find_xplane(TRACE_DIR)


def scope_ms(rctx, name: str, *paths: str) -> float | None:
    """Device milliseconds under the scope paths per execution of
    `jit_<name>` in the traced window, or None where the program ran no
    op under any of them."""
    count = rctx.trace.executions(name)
    if not count:
        return None
    got = reduce(traced_file()).total(name, *paths)
    return None if got is None else got / count * 1e3
