"""Back-to-back `krige` requests on the tile engine; one client, one
chip.  Each request holds out a random `holdout_fraction` of one field
and predicts it from the other sites at a theta drawn from `theta_box`."""

from __future__ import annotations

import numpy as np
import jax

from .. import data, program, reference
from ..drivers import Ctx, closed_loop, compile_exe, put, sample

CHIPS = (1,)


def krige_fn(ctx: Ctx):
    m, policy, nb = ctx.model, ctx.policy, ctx.nb

    def cb_krige(locs, z, obs, new, theta):
        return program.krige(locs[obs], z[obs], locs[new], theta, policy,
                             nb=nb, nu_static=m["nu"], jitter=m["jitter"])

    return cb_krige


class Driver:
    def __init__(self, ctx: Ctx):
        self.ctx = ctx

    def setup(self):
        ctx, c, tr = self.ctx, self.ctx.config, self.ctx.traffic
        f = tr["holdout_fraction"]
        self.m = round(ctx.n * f / (1.0 - f))
        total = ctx.n + self.m
        with ctx.timed("draw"):
            self.locs_h, self.z_h = data.field(ctx.seed, 0, total, c["theta0"],
                                               c["nu"], c["jitter"])
            gen = data.rng(ctx.seed, 1)
            self.thetas_h = data.box_draw(gen, tr["theta_box"], tr["requests"])
            self.splits = []
            for _ in range(tr["requests"]):
                new = np.sort(gen.choice(total, size=self.m, replace=False))
                obs = np.setdiff1d(np.arange(total), new)   # Morton order kept
                self.splits.append((obs.astype(np.int32), new.astype(np.int32)))
            self.locs, self.z = put(self.locs_h, self.z_h)
            self.inputs = [(self.locs, self.z, *put(o, nw), jax.device_put(th))
                           for (o, nw), th in zip(self.splits, self.thetas_h)]
        self.exe = compile_exe(ctx, krige_fn(ctx), *self.inputs[0])
        with ctx.timed("warm"):
            for _ in range(2):
                jax.block_until_ready(self.exe(*self.inputs[0]))

    def window(self, seconds: float):
        self.times, outs = closed_loop(self.exe, self.inputs, seconds,
                                       "cb.request", self.ctx.trace_s)
        self.mus = [np.asarray(o) for o in outs]
        self.ctx.counters["requests"] = len(self.mus)

    def free(self):
        for name in ("exe", "inputs", "locs", "z"):
            self.__dict__.pop(name, None)

    def outcome(self):
        return len(self.mus), sum(not np.all(np.isfinite(m)) for m in self.mus)

    def end_to_end(self) -> dict:
        t = self.times
        return {"predict_s": (t[-1][1] - t[0][0]) / len(t)}

    def checks(self) -> dict:
        c, m = self.ctx.config, self.ctx.model
        err = 0.0
        for i in sample(self.ctx, len(self.mus)):
            obs, new = self.splits[i % len(self.splits)]
            th = self.thetas_h[i % len(self.thetas_h)]
            ref = reference.krige_mean(self.locs_h[obs], self.z_h[obs],
                                       self.locs_h[new], th, **m)
            err = max(err, reference.mean_rel_err(self.mus[i], ref))
        return {"mean_rel_err": (err, c["limits"]["mean_rel_err"]),
                "nonfinite": (self.outcome()[1], 0)}
