"""Precision policies for the mixed-precision tile Cholesky (paper Sec. VI).

The paper's policy: tiles with tile-index distance |i - j| < diag_thick from
the diagonal operate in double precision ("DP"); all farther tiles operate in
single precision ("SP").  On TPU there is no fast fp64, so the production
pair is {hi=fp32, lo=bf16}; the paper's literal {fp64, fp32} pair is kept for
CPU statistical validation (see DESIGN.md "Hardware adaptation").

The policy also covers:
  * "full"  -- DP(100%), the paper's reference baseline;
  * "dst"   -- Diagonal-Super-Tile / independent-blocks tapering baseline
               (off-band set to ZERO, paper Sec. V-B);
  * "three_tier" -- the paper's stated future work: hi / lo / lo2 (fp8) with
               two distance thresholds (beyond-paper deliverable).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    mode: str                 # "full" | "mixed" | "dst" | "three_tier"
    hi: Any                   # band dtype
    lo: Any                   # off-band dtype ("mixed"/"three_tier")
    diag_thick: int           # band half-width in tiles (>= 1)
    lo2: Any = None           # far-off-band dtype ("three_tier")
    diag_thick2: int = 0      # second threshold in tiles ("three_tier")
    solve_dtype: Any = jnp.float32  # dtype lo-precision TRSMs execute in
    accum_dtype: Any = jnp.float32  # accumulator for lo GEMMs (MXU semantics)

    def __post_init__(self):
        if self.mode not in ("full", "mixed", "dst", "three_tier"):
            raise ValueError(f"unknown policy mode {self.mode!r}")
        if self.diag_thick < 1:
            raise ValueError(f"diag_thick must be >= 1, got {self.diag_thick}")
        for field in ("solve_dtype", "accum_dtype"):
            value = getattr(self, field)
            try:
                dt = jnp.dtype(value)
            except TypeError as e:
                raise ValueError(f"{field} is not a dtype: {value!r}") from e
            if not jnp.issubdtype(dt, jnp.floating):
                raise ValueError(
                    f"{field} must be a floating dtype, got {dt}")
        # a narrower accumulator than the lo storage would silently round
        # every MXU partial product below the paper's SP error model
        try:
            lo_bits = jnp.finfo(jnp.dtype(self.lo)).bits
        except (TypeError, ValueError):
            lo_bits = None  # non-float lo is caught by downstream tile math
        accum_bits = jnp.finfo(jnp.dtype(self.accum_dtype)).bits
        if lo_bits is not None and accum_bits < lo_bits:
            raise ValueError(
                f"accum_dtype ({jnp.dtype(self.accum_dtype)}, {accum_bits} "
                f"bits) must be at least as wide as lo "
                f"({jnp.dtype(self.lo)}, {lo_bits} bits)")
        if self.mode == "three_tier":
            if self.lo2 is None:
                raise ValueError("three_tier policy needs a lo2 dtype")
            if self.diag_thick2 <= self.diag_thick:
                # diag_thick2 == diag_thick would silently erase the lo tier;
                # ask for an explicit two-tier policy instead
                raise ValueError(
                    f"three_tier needs diag_thick2 > diag_thick, got "
                    f"diag_thick2={self.diag_thick2} <= "
                    f"diag_thick={self.diag_thick}")

    # ---- constructors -------------------------------------------------
    @staticmethod
    def full(hi=jnp.float32) -> "PrecisionPolicy":
        """DP(100%): the paper's reference."""
        return PrecisionPolicy(mode="full", hi=hi, lo=hi, diag_thick=1 << 30,
                               solve_dtype=hi, accum_dtype=hi)

    @staticmethod
    def paper_cpu(diag_thick: int) -> "PrecisionPolicy":
        """The paper's literal pair: DP=fp64 band, SP=fp32 off-band.

        Requires x64 (use `with jax.enable_x64(True):` or the config flag).
        """
        return PrecisionPolicy(mode="mixed", hi=jnp.float64, lo=jnp.float32,
                               diag_thick=diag_thick,
                               solve_dtype=jnp.float32, accum_dtype=jnp.float32)

    @staticmethod
    def tpu(diag_thick: int) -> "PrecisionPolicy":
        """TPU-native pair: hi=fp32 band, lo=bf16 off-band, fp32 accumulate."""
        return PrecisionPolicy(mode="mixed", hi=jnp.float32, lo=jnp.bfloat16,
                               diag_thick=diag_thick,
                               solve_dtype=jnp.float32, accum_dtype=jnp.float32)

    @staticmethod
    def dst(diag_thick: int, hi=jnp.float32) -> "PrecisionPolicy":
        """Diagonal-Super-Tile tapering: off-band ZERO (independent blocks)."""
        return PrecisionPolicy(mode="dst", hi=hi, lo=hi, diag_thick=diag_thick,
                               solve_dtype=hi, accum_dtype=hi)

    @staticmethod
    def three_tier(diag_thick: int, diag_thick2: int) -> "PrecisionPolicy":
        """fp32 band / bf16 mid / fp8(e4m3) far -- the paper's future work."""
        return PrecisionPolicy(mode="three_tier", hi=jnp.float32,
                               lo=jnp.bfloat16, lo2=jnp.float8_e4m3fn,
                               diag_thick=diag_thick, diag_thick2=diag_thick2,
                               solve_dtype=jnp.float32, accum_dtype=jnp.float32)

    # ---- tile classification ------------------------------------------
    def tile_dtype(self, i: int, j: int):
        """Storage dtype of tile (i, j) (tile indices)."""
        d = abs(i - j)
        if self.mode == "full":
            return self.hi
        if d < self.diag_thick:
            return self.hi
        if self.mode == "three_tier" and d >= self.diag_thick2:
            return self.lo2
        if self.mode == "dst":
            return None  # zeroed / dropped
        return self.lo

    def in_band(self, i: int, j: int) -> bool:
        return abs(i - j) < self.diag_thick or self.mode == "full"

    def dp_fraction(self, p: int) -> float:
        """Fraction of lower-triangle tiles inside the DP band (for the
        paper's DP(x%)-SP(y%) labels)."""
        total = p * (p + 1) // 2
        t = min(self.diag_thick, p)
        band = t * p - t * (t - 1) // 2
        return band / total

    @staticmethod
    def from_dp_percent(p: int, dp_percent: float, pair: str = "tpu") -> "PrecisionPolicy":
        """Build a policy whose band covers ~dp_percent of the lower tiles.

        Matches the paper's DP(x%)-SP(y%) naming: solves for diag_thick t
        such that band_tiles / total_tiles ~ x%.
        """
        total = p * (p + 1) / 2
        best_t, best_err = 1, float("inf")
        for t in range(1, p + 1):
            frac = (t * p - t * (t - 1) / 2) / total
            err = abs(frac - dp_percent)
            if err < best_err:
                best_t, best_err = t, err
        ctor = {"tpu": PrecisionPolicy.tpu, "paper_cpu": PrecisionPolicy.paper_cpu,
                "dst": PrecisionPolicy.dst}[pair]
        return ctor(best_t)


def lo_matmul(a, b, policy: PrecisionPolicy, tier=None):
    """Low-precision GEMM with explicit accumulator semantics.

    paper_cpu pair: fp32 x fp32 -> fp32 (literal sgemm).
    tpu pair:       bf16 x bf16 -> fp32 accumulate (MXU), round to bf16.
    """
    lo = tier if tier is not None else policy.lo
    a = a.astype(lo)
    b = b.astype(lo)
    out = jnp.matmul(a, b, preferred_element_type=policy.accum_dtype)
    return out.astype(lo)
