"""Back-to-back `geostat_loglik_step` calls, one jitted program, each at
a theta drawn from the mix's `theta_box`; one client, one chip."""

from __future__ import annotations

import numpy as np
import jax

from .. import data, program, reference
from ..drivers import Ctx, closed_loop, compile_exe, put, sample

CHIPS = (1,)


def eval_fn(ctx: Ctx):
    """`cb_eval(locs, z, theta)`: the program's likelihood step with the
    cell's tile size and policy; the field and theta are arguments."""
    m, policy, nb = ctx.model, ctx.policy, ctx.nb

    def cb_eval(locs, z, theta):
        return program.geostat_loglik_step(
            locs, z, theta, nb=nb, policy=policy, nu_static=m["nu"],
            jitter=m["jitter"])

    return cb_eval


class Driver:
    def __init__(self, ctx: Ctx):
        self.ctx = ctx

    def setup(self):
        ctx, c = self.ctx, self.ctx.config
        with ctx.timed("draw"):
            self.locs_h, self.z_h = data.field(ctx.seed, 0, ctx.n, c["theta0"],
                                               c["nu"], c["jitter"])
            self.thetas_h = data.box_draw(data.rng(ctx.seed, 1),
                                          ctx.traffic["theta_box"],
                                          ctx.traffic["thetas"])
            self.locs, self.z = put(self.locs_h, self.z_h)
            self.thetas = [(self.locs, self.z, jax.device_put(th))
                           for th in self.thetas_h]
        args = self.thetas[0]
        self.exe = compile_exe(ctx, eval_fn(ctx), *args)
        with ctx.timed("warm"):
            for _ in range(2):
                jax.block_until_ready(self.exe(*args))

    def window(self, seconds: float):
        self.times, outs = closed_loop(self.exe, self.thetas, seconds,
                                       "cb.eval", self.ctx.trace_s)
        self.lls = np.array([float(o) for o in outs])
        self.ctx.counters["evals"] = len(self.lls)

    def free(self):
        for name in ("exe", "locs", "z", "thetas"):
            self.__dict__.pop(name, None)

    def outcome(self):
        lls = self.lls
        return len(lls), int(np.sum(~np.isfinite(lls)))

    def end_to_end(self) -> dict:
        t = self.times
        return {"eval_s": (t[-1][1] - t[0][0]) / len(t)}

    def checks(self) -> dict:
        c, m = self.ctx.config, self.ctx.model
        drift = 0.0
        for i in sample(self.ctx, len(self.lls)):
            th = self.thetas_h[i % len(self.thetas_h)]
            ref = reference.loglik(self.locs_h, self.z_h, th, **m)
            drift = max(drift, reference.ll_drift(self.lls[i], ref))
        return {"ll_drift": (drift, c["limits"]["ll_drift"]),
                "nonfinite": (self.outcome()[1], 0)}
