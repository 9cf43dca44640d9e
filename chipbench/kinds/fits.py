"""Back-to-back `fit_mle` fits (Nelder-Mead, host loop), each driven
through the jitted likelihood step; one client, one chip.

Every run fits the same `fields` replicate fields (drawn from the mix's
`fields_seed`) from the same starts, in the same order, cycled: the
evaluations a fit takes depend on its field and start, so each window
does the same work whatever its seed.  The seed draws which of the
window's evaluations are compared with the reference.
"""

from __future__ import annotations

import statistics
import time

import numpy as np
import jax
from jax.profiler import TraceAnnotation

from .. import data, program, reference
from ..drivers import Ctx, Window, WindowClosed, compile_exe, put, sample
from .evals import eval_fn

CHIPS = (1,)


class Driver:
    def __init__(self, ctx: Ctx):
        self.ctx = ctx

    def setup(self):
        ctx, c, tr = self.ctx, self.ctx.config, self.ctx.traffic
        with ctx.timed("draw"):
            base = tr["fields_seed"]
            self.fields_h = [data.field(base, 10 + r, ctx.n, c["theta0"],
                                        c["nu"], c["jitter"])
                             for r in range(tr["fields"])]
            self.starts = data.box_draw(data.rng(base, 1), tr["start_box"],
                                        tr["fields"])
            self.fields = [tuple(put(l, z)) for l, z in self.fields_h]
        theta = jax.device_put(self.starts[0])
        self.exe = compile_exe(ctx, eval_fn(ctx), *self.fields[0], theta)
        with ctx.timed("warm"):
            jax.block_until_ready(self.exe(*self.fields[0], theta))
            # one short fit: the host loop's own small programs compile here
            self._fit(0, max_iters=2, record=None)

    def _fit(self, r: int, *, max_iters: int, record):
        f = r % len(self.fields)
        locs, z = self.fields[f]

        def loglik(theta):
            if record is not None and record["closed"]:
                raise WindowClosed
            with TraceAnnotation("cb.eval"):
                t0 = time.perf_counter()
                out = jax.block_until_ready(self.exe(locs, z, theta))
                t1 = time.perf_counter()
            if record is not None:
                record["evals"].append((f, theta, out, t0, t1))
                record["closed"] = record["window"].closed(t1)
            return out

        return program.fit_mle(loglik, self.starts[f],
                               xtol=self.ctx.traffic["xtol"],
                               max_iters=max_iters, jit=False)

    def window(self, seconds: float):
        rec = {"evals": [], "closed": False}
        self.fits = []      # (start, end, n_evals, converged, first, last eval)
        with Window(seconds, self.ctx.trace_s) as window:
            rec["window"] = window
            r = 0
            while not rec["closed"]:
                first = len(rec["evals"])
                t0 = time.perf_counter()
                try:
                    with TraceAnnotation("cb.fit"):
                        res = self._fit(r, record=rec,
                                        max_iters=self.ctx.traffic["max_iters"])
                except WindowClosed:
                    break
                self.fits.append((t0, time.perf_counter(), res.n_evals,
                                  res.converged, first, len(rec["evals"])))
                r += 1
        self.evals = [(f, np.asarray(th, np.float32), float(out), t0, t1)
                      for f, th, out, t0, t1 in rec["evals"]]
        cnt = self.ctx.counters
        cnt["evals"] = len(self.evals)
        cnt["fits"] = len(self.fits)
        if self.fits:
            cnt["evals_per_fit"] = sum(f[2] for f in self.fits) / len(self.fits)
        ev = self.evals
        # the host's share of an evaluation, for the run's log
        cnt["eval_call_ms"] = 1e3 * float(np.median([b - a for *_, a, b in ev]))
        cnt["between_evals_ms"] = 1e3 * float(np.median(
            [b[3] - a[4] for a, b in zip(ev, ev[1:])] or [0.0]))

    def free(self):
        for name in ("exe", "fields"):
            self.__dict__.pop(name, None)

    def outcome(self):
        bad = 0
        for _, _, _, converged, a, b in self.fits:
            finite = all(np.isfinite(e[2]) for e in self.evals[a:b])
            bad += (not converged) or (not finite)
        return len(self.fits), bad

    def end_to_end(self) -> dict:
        lat = [t1 - t0 for *_, t0, t1 in self.evals]
        out = {"eval_p95_s": statistics.quantiles(lat, n=20)[18]}
        if self.fits:
            out["fit_s"] = (self.fits[-1][1] - self.fits[0][0]) / len(self.fits)
        return out

    def checks(self) -> dict:
        c, m = self.ctx.config, self.ctx.model
        drift = 0.0
        for i in sample(self.ctx, len(self.evals)):
            f, th, ll, _, _ = self.evals[i]
            locs, z = self.fields_h[f]
            drift = max(drift, reference.ll_drift(
                ll, reference.loglik(locs, z, th, **m)))
        nonfinite = sum(not np.isfinite(e[2]) for e in self.evals)
        return {"ll_drift": (drift, c["limits"]["ll_drift"]),
                "nonfinite": (nonfinite, 0)}
