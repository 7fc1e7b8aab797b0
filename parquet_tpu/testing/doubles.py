"""The plain reference for DOUBLE columns delivered under `doubles=`
(kernels/pipeline.py DOUBLE_FORMS): what pyarrow's read of the same file plus
numpy give, as the unsigned bit patterns the comparison is made on.

    "bits"     float64.view(uint64)
    "float32"  float64.astype(float32).view(uint32) — IEEE round to nearest
               even, +-inf on overflow, f32 subnormals, -0.0 kept

A NaN stays a NaN and its payload is not compared. Host only: numpy."""

from __future__ import annotations

import numpy as np

__all__ = ["adversarial_doubles", "double_patterns", "same_double_form"]


def double_patterns(values, form: str) -> np.ndarray:
    """float64 `values` as the unsigned patterns of their delivered form."""
    v = np.ascontiguousarray(values, dtype=np.float64)
    if form == "bits":
        return v.view(np.uint64)
    if form != "float32":
        raise ValueError(f"unknown double form {form!r}")
    with np.errstate(over="ignore", invalid="ignore"):
        return v.astype(np.float32).view(np.uint32)


def same_double_form(got, values, form: str) -> bool:
    """Is the delivered array `got` (uint64 under "bits", float32 under
    "float32") the stated function of the float64 `values`, bit for bit,
    NaN for NaN?"""
    got = np.asarray(got)
    want = double_patterns(values, form)
    if got.dtype != (np.uint64 if form == "bits" else np.float32) or got.shape != want.shape:
        return False
    nan = np.isnan(np.asarray(values, dtype=np.float64))
    got_u = got.view(want.dtype)
    got_nan = np.isnan(got_u.view(np.float64 if form == "bits" else np.float32))
    return bool(np.array_equal(got_nan, nan) and np.array_equal(got_u[~nan], want[~nan]))


def adversarial_doubles(seed: int = 0, n: int = 4096) -> dict:
    """Named families of float64 bit patterns (uint64 arrays of about `n`)
    on which a float64 -> float32 narrowing goes wrong first."""
    rng = np.random.default_rng(seed)
    f32 = rng.standard_normal(n).astype(np.float32) * np.float32(10.0) ** rng.integers(-30, 30, n)
    exact = f32.astype(np.float64).view(np.uint64)  # low 29 bits zero
    half = np.uint64(1 << 28)
    k = rng.integers(0, 1 << 23, n).astype(np.float64)
    fmax = float(np.finfo(np.float32).max)
    tiny = (rng.uniform(0.0, 1.0, n) * 2.0 ** rng.integers(-160, -120, n)) * rng.choice([-1.0, 1.0], n)
    return {
        "random_bits": rng.integers(0, 1 << 64, n, dtype=np.uint64),
        "random_values": (rng.standard_normal(n) * 10.0 ** rng.uniform(-60, 60, n)).view(np.uint64),
        "money": np.round(rng.gamma(2.0, 9.0, n), 2).view(np.uint64),
        "ties": np.concatenate([exact | half, (exact | half) + np.uint64(1), (exact | half) - np.uint64(1)]),
        "sticky": exact | np.uint64((1 << 29) - 1),
        "subnormal": tiny.view(np.uint64),
        "subnormal_ties": np.concatenate([((k + 0.5) * 2.0**-149).view(np.uint64),
                                          ((k + 0.5) * 2.0**-149).view(np.uint64) + np.uint64(1)]),
        "overflow": np.array([fmax, np.nextafter(fmax, np.inf), fmax + 2.0**103, fmax + 2.0**103 - 2.0**60,
                              -fmax - 2.0**103, 1e300, -1e300, 2.0**128, np.nextafter(2.0**128, 0)]).view(np.uint64),
        "edges": np.array([0.0, -0.0, np.inf, -np.inf, 5e-324, -5e-324, 2.0**-150, np.nextafter(2.0**-150, 1.0),
                           2.0**-149, 2.0**-126, np.nextafter(2.0**-126, 0.0), 1.0, -1.0, 19.88, 0.1]).view(np.uint64),
        "nan": np.array([0x7FF0000000000001, 0xFFF8000000000000, 0x7FF4000000000000,
                         0x7FFFFFFFFFFFFFFF, 0x7FF8000000000000], dtype=np.uint64),
    }
