"""Bedrock input velocities from the seed (the ``band_noise`` family).

A copy of the program's ``scenario.catalog.WaveSpec("band_noise")``
synthesis (reached from ``surrogate.dataset.random_band_limited_waves``),
kept here so that no change to the program moves the benchmark's inputs.
A traffic file (``bench/traffic/<name>.json``) gives the parameters."""
from __future__ import annotations

import numpy as np


def cosine_taper(nt: int, frac: float) -> np.ndarray:
    w = np.ones(nt)
    if frac <= 0.0:
        return w
    m = max(1, int(round(frac * nt)))
    if 2 * m >= nt:
        m = nt // 2
    ramp = 0.5 * (1.0 - np.cos(np.pi * (np.arange(m) + 0.5) / m))
    w[:m] = ramp
    w[nt - m:] = ramp[::-1]
    return w


def band_noise(n: int, nt: int, dt: float, seed: int, *, fmax: float,
               amp_xy: float, amp_z: float, taper_frac: float) -> np.ndarray:
    """``[n, nt, 3]`` zero-mean, tapered, band-limited velocities (float64)."""
    rng = np.random.default_rng(seed)
    amp = np.array([amp_xy, amp_xy, amp_z])
    w = rng.uniform(-1.0, 1.0, size=(n, nt, 3)) * amp
    w = w * cosine_taper(nt, taper_frac)[None, :, None]
    freqs = np.fft.rfftfreq(nt, dt)
    kill = (freqs > fmax) | (freqs == 0.0)
    W = np.fft.rfft(w, axis=1)
    W[:, kill] = 0.0
    return np.fft.irfft(W, n=nt, axis=1)


def window_waves(traffic: dict, n_cases: int, dt: float, record_steps: int,
                 seed: int) -> tuple[np.ndarray, int]:
    """The steps a run feeds, ``[n_cases, max_steps, 3]``, and their offset
    into the records.

    The records (one per case) and the offset, inside the records'
    untapered middle, come from the traffic's ``record_seed``: every run
    does the same work, as a campaign over a fixed catalogue of motions
    does.  The run's ``seed`` assigns the records to the case slots and
    flips the sign of each: the multispring law (odd backbone, Masing
    branches, virgin start) and the solver are exactly odd in the input, so
    a flipped record costs the same iterations and gives the negated
    response, to the bit."""
    max_steps = int(traffic["max_steps"])
    rseed = int(traffic["record_seed"])
    rec = band_noise(n_cases, record_steps, dt, rseed, fmax=traffic["fmax"],
                     amp_xy=traffic["amp_xy"], amp_z=traffic["amp_z"],
                     taper_frac=traffic["taper_frac"])
    m = int(round(traffic["taper_frac"] * record_steps))
    lo, hi = m, record_steps - m - max_steps
    if hi <= lo:
        raise ValueError("record too short for max_steps outside the taper")
    off = int(np.random.default_rng([rseed, 1]).integers(lo, hi))
    rng = np.random.default_rng(seed)
    order = rng.permutation(n_cases)
    sign = rng.choice([-1.0, 1.0], size=n_cases)
    return rec[order, off:off + max_steps] * sign[:, None, None], off
