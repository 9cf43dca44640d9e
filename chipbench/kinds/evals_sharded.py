"""Back-to-back calls of the sharded likelihood on a 2x2 mesh of four
chips, one jitted program, each at a theta drawn from the mix's
`theta_box`; one client.

The program is `geostat_loglik_distributed` with the `fori` sweep, the
path `chip_smoke.py --chips 4` proved: the rows of `locs` and `z` split
over the mesh's "data" axis, theta on every chip.  The field is an exact
fp64 draw at a theta* drawn from the box, and every `theta_star_every`-th
call of the cycle is at theta*, where the draw also gave the exact
log-likelihood (`draw_reference`): one host factorization per run, and
the comparison after the window costs nothing.  In a rehearsal on fewer
than four devices the mesh is the one-device mesh with the same axes.
"""

from __future__ import annotations

import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import data, draw_reference, program_sharded, reference
from ..drivers import Ctx, compile_exe
from . import evals

CHIPS = (4,)
VERSION = "fori"


def make_mesh():
    """The 2x2 ("data", "model") mesh of the first four devices, or the
    1x1 mesh with those axes where JAX has fewer."""
    devs = jax.devices()
    if len(devs) >= 4:
        return program_sharded.make_geostat_mesh(devs[:4])
    return program_sharded.make_smoke_mesh()


def eval_fn(ctx: Ctx):
    """`cb_eval4(locs, z, theta)`: the program's sharded likelihood with
    the cell's tile size and policy; the field and theta are arguments."""
    m, policy, nb = ctx.model, ctx.policy, ctx.nb

    def cb_eval4(locs, z, theta):
        return program_sharded.geostat_loglik_distributed(
            locs, z, theta, nb=nb, policy=policy, nu_static=m["nu"],
            jitter=m["jitter"], version=VERSION)

    return cb_eval4


class Driver(evals.Driver):
    """The `evals` driver's window and readings on the sharded program; its
    own set-up (mesh, draw with reference) and comparison at theta*."""

    def setup(self):
        ctx, c, tr = self.ctx, self.ctx.config, self.ctx.traffic
        mesh = make_mesh()
        rows = NamedSharding(mesh, P("data", None))
        vec = NamedSharding(mesh, P("data"))
        rep = NamedSharding(mesh, P())
        # compiled before the draw: a program that cannot be built fails
        # in seconds, not after minutes of host factorization
        shapes = (jax.ShapeDtypeStruct((ctx.n, 2), np.float32, sharding=rows),
                  jax.ShapeDtypeStruct((ctx.n,), np.float32, sharding=vec),
                  jax.ShapeDtypeStruct((2,), np.float32, sharding=rep))
        program_sharded.set_activation_mesh(mesh)
        try:
            self.exe = compile_exe(ctx, eval_fn(ctx), *shapes)
        finally:
            program_sharded.set_activation_mesh(None)
        with ctx.timed("draw"):
            self.theta_star = data.box_draw(data.rng(ctx.seed, 3),
                                            tr["theta_box"], 1)[0]
            locs, z, self.ll_ref = draw_reference.field_and_loglik(
                ctx.seed, 0, ctx.n, self.theta_star, c["nu"], c["jitter"])
            thetas = data.box_draw(data.rng(ctx.seed, 1), tr["theta_box"],
                                   tr["thetas"])
            thetas[::tr["theta_star_every"]] = self.theta_star
            self.locs = jax.device_put(locs, rows)
            self.z = jax.device_put(z, vec)
            self.thetas = [(self.locs, self.z, jax.device_put(th, rep))
                           for th in thetas]
        with ctx.timed("warm"):
            for _ in range(2):
                jax.block_until_ready(self.exe(*self.thetas[0]))

    def at_theta_star(self) -> np.ndarray:
        """Indices of the window's evaluations at theta*."""
        tr = self.ctx.traffic
        i = np.arange(len(self.lls))
        return i[(i % tr["thetas"]) % tr["theta_star_every"] == 0]

    def checks(self) -> dict:
        c = self.ctx.config
        drift = max(reference.ll_drift(self.lls[i], self.ll_ref)
                    for i in self.at_theta_star())
        return {"ll_drift": (drift, c["limits"]["ll_drift"]),
                "nonfinite": (self.outcome()[1], 0)}
