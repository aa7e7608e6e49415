"""Convolution + overlap-save tiling specification."""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    """Static geometry of one FFT-based convolution.

    Overlap-save with tile ``delta x delta``: every tile of the padded input
    yields a ``t x t`` block of valid outputs, ``t = delta - k + 1``.
    ``stride`` subsamples the output in both axes.  The tile grid covers
    the unit-stride output whatever the stride: overlap-save computes
    every unit-stride output, so only ``direct`` runs a strided spec.
    """
    B: int
    C: int
    Cout: int
    H: int
    W: int
    kh: int
    kw: int
    pad_h: int = 0
    pad_w: int = 0
    delta: int = 16
    stride: int = 1

    def __post_init__(self):
        if self.stride < 1:
            raise ValueError(f"stride must be >= 1, got {self.stride}")
        if self.kh > self.delta or self.kw > self.delta:
            raise ValueError(
                f"kernel {self.kh}x{self.kw} exceeds tile size {self.delta}")

    # ---- derived geometry -------------------------------------------------
    @property
    def t_h(self) -> int:              # valid outputs per tile, rows
        return self.delta - self.kh + 1

    @property
    def t_w(self) -> int:
        return self.delta - self.kw + 1

    @property
    def Ho(self) -> int:
        return (self.H + 2 * self.pad_h - self.kh) // self.stride + 1

    @property
    def Wo(self) -> int:
        return (self.W + 2 * self.pad_w - self.kw) // self.stride + 1

    @property
    def X(self) -> int:                # tile grid rows
        return math.ceil((self.H + 2 * self.pad_h - self.kh + 1) / self.t_h)

    @property
    def D(self) -> int:                # tile grid cols (paper's Delta)
        return math.ceil((self.W + 2 * self.pad_w - self.kw + 1) / self.t_w)

    @property
    def n_tiles(self) -> int:
        return self.X * self.D

    @property
    def M(self) -> int:                # CGEMM row count: B * X * Delta
        return self.B * self.n_tiles

    # padded input extent covered by the tile grid (>= H + 2*pad)
    @property
    def Hp(self) -> int:
        return (self.X - 1) * self.t_h + self.delta

    @property
    def Wp(self) -> int:
        return (self.D - 1) * self.t_w + self.delta

    def freq_points(self) -> int:
        """Stored frequency points of a tile's spectrum: the compact
        Hermitian list ``repro.core.dft``'s folded matrices produce and
        consume, delta^2/2 + 2 for even delta and (delta^2 + 1)/2 for odd
        (130 at delta=16, just over half the full spectrum)."""
        d = self.delta
        return d * d // 2 + 2 if d % 2 == 0 else (d * d + 1) // 2

    # ---- cost model: what the plans run -----------------------------------
    # FLOPs count every multiply and add of the stage as the plans lower it
    # (``tests/test_conv_plan.py`` holds them to XLA's ``cost_analysis``);
    # bytes count each stage's HBM traffic at 4 bytes a float32 value.
    def direct_flops(self) -> int:
        return 2 * self.B * self.Cout * self.C * self.Ho * self.Wo * self.kh * self.kw

    def direct_bytes(self) -> int:
        """x and k read, y written."""
        return 4 * (self.B * self.C * self.H * self.W
                    + self.Cout * self.C * self.kh * self.kw
                    + self.B * self.Cout * self.Ho * self.Wo)

    def cgemm_flops(self, three_m: bool = False) -> int:
        """Per point: 3M is three real matmuls, the operand sums Dr + Di
        and Gr + Gi, and three output subtractions; 4M four matmuls and
        two output adds."""
        M, C, Co = self.M, self.C, self.Cout
        if three_m:
            per_point = 6 * M * C * Co + M * C + C * Co + 3 * M * Co
        else:
            per_point = 8 * M * C * Co + 2 * M * Co
        return self.freq_points() * per_point

    def _tile_dft_flops(self) -> tuple:
        """(forward per real delta x delta tile, inverse per output tile):
        one (d*d, P) matmul per plane forward; zr @ Kr + zi @ Ki against
        the cropped (P, t_h*t_w) mats inverse."""
        d, P, t = self.delta, self.freq_points(), self.t_h * self.t_w
        return 4 * d * d * P, 4 * P * t + t

    def stage_flops(self, three_m: bool = True) -> dict:
        """FLOPs of each FFT stage (stage 2 once per weight version)."""
        fwd, inv = self._tile_dft_flops()
        return {"input_transform": self.M * self.C * fwd,
                "kernel_transform": self.C * self.Cout * fwd,
                "cgemm": self.cgemm_flops(three_m=three_m),
                "output_inverse": self.M * self.Cout * inv}

    def stage_bytes(self) -> dict:
        """HBM bytes of each FFT stage a prepared forward runs: stage 1
        reads x, writes and reads the overlap-save tiles and writes the
        spectrum; stage 3 reads the spectrum and G and writes Z; stage 4
        reads Z and writes y."""
        P, d = self.freq_points(), self.delta
        x = self.B * self.C * self.H * self.W
        y = self.B * self.Cout * self.Ho * self.Wo
        D, G, Z = (2 * P * self.M * self.C, 2 * P * self.C * self.Cout,
                   2 * P * self.M * self.Cout)
        return {k: 4 * v for k, v in (
            ("input_transform", x + 2 * self.M * d * d * self.C + D),
            ("cgemm", D + G + Z),
            ("output_inverse", Z + y))}
