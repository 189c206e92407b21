"""Pallas TPU kernel for the multi-spring constitutive update.

TPU adaptation of the paper's CUDA multi-spring kernel (DESIGN.md §2):

* **evaluation points on the 128-lane axis, springs on sublanes** — a block
  is ``[S, TILE_P]``.  At the paper's 150 springs this pads S to 152
  sublanes, where springs on lanes would pad to 256.  The wrapper
  transposes the ``[P, S]`` state; whether XLA then stores it as
  ``[S, P]`` in HBM, so that the transposes cost no copy, is not verified;
* the per-spring Masing branch logic (SIMT divergent threads on the GPU)
  becomes **lane predication** (`jnp.where`), which is exactly how the VPU
  executes divergent element-wise control flow;
* the two reductions over springs — σ = nᵀ(w·τ) and the tangent assembly
  D = Σ w·G_tan·(n⊗n) — are ``[6,S] @ [S,TILE_P]`` and
  ``[36,S] @ [S,TILE_P]`` matmuls: they land on the **MXU**, which the
  scalar-per-thread GPU formulation cannot do.

Each grid step processes TILE_P evaluation points; the full spring state for
those points streams HBM→VMEM→HBM once — the kernel is the compute stage of
the Algorithm-3 pipeline, so its block size is the unit the heterogeneous
memory manager streams from host memory.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_HIGHEST = jax.lax.Precision.HIGHEST


def _ms_kernel(
    eps_ref, grev_ref, trev_ref, gprev_ref, gmax_ref, dir_ref, virg_ref,
    g0_ref, gr_ref, be_ref, bulk_ref, n_ref, nt_ref, nnt_ref, w_ref,
    # outputs
    sig_ref, d_ref, frac_ref,
    ngrev_ref, ntrev_ref, ngprev_ref, ngmax_ref, ndir_ref, nvirg_ref,
):
    """One TILE_P block of evaluation points (points on lanes).

    eps [6,T] · state [S,T] · params [1,T] · n [S,6] (+ nᵀ [6,S],
    (n⊗n)ᵀ [36,S], w [S,1]) → σ [6,T], D [36,T], frac [1,T], new state [S,T].
    """
    eps = eps_ref[...]
    G0 = g0_ref[...]       # [1,T]
    gr = gr_ref[...]
    be = be_ref[...]
    bulk = bulk_ref[...]
    n = n_ref[...]         # [S,6]
    nT = nt_ref[...]       # [6,S]
    nnT = nnt_ref[...]     # [36,S]
    w = w_ref[...]         # [S,1]
    dot = functools.partial(jnp.dot, preferred_element_type=eps.dtype, precision=_HIGHEST)

    def backbone(g):
        x = jnp.abs(g) / gr
        return G0 * g / (1.0 + x**be)

    def backbone_tan(g):
        x = jnp.abs(g) / gr
        den = 1.0 + x**be
        return G0 * (1.0 + (1.0 - be) * x**be) / (den * den)

    gamma = dot(n, eps)  # [S,T] MXU
    g_prev = gprev_ref[...]
    dgam = gamma - g_prev
    moving = jnp.sign(dgam).astype(jnp.int32)
    dir_old = dir_ref[...]
    virgin_old = virg_ref[...] == 1

    tau_prev = jnp.where(
        virgin_old,
        backbone(g_prev),
        trev_ref[...] + 2.0 * backbone(0.5 * (g_prev - grev_ref[...])),
    )
    reversal = (moving != 0) & (dir_old != 0) & (moving != dir_old)
    gamma_rev = jnp.where(reversal, g_prev, grev_ref[...])
    tau_rev = jnp.where(reversal, tau_prev, trev_ref[...])
    direction = jnp.where(moving != 0, moving, dir_old)
    virgin = jnp.where(reversal, 0, virg_ref[...])

    gmax = gmax_ref[...]
    rejoin = jnp.abs(gamma) >= gmax
    virgin = jnp.where(rejoin, 1, virgin)
    gamma_max = jnp.maximum(gmax, jnp.abs(gamma))

    on_bb = virgin == 1
    tau = jnp.where(on_bb, backbone(gamma), tau_rev + 2.0 * backbone(0.5 * (gamma - gamma_rev)))
    g_tan = jnp.where(on_bb, backbone_tan(gamma), backbone_tan(0.5 * (gamma - gamma_rev)))
    g_tan = jnp.maximum(g_tan, 1e-3 * G0)

    sigma_dev = dot(nT, tau * w)    # [6,T] MXU
    D_dev = dot(nnT, g_tan * w)     # [36,T] MXU

    vol = eps[0:1] + eps[1:2] + eps[2:3]  # [1,T]
    # volumetric masks built from iota (kernels may not capture constants)
    i6 = jax.lax.broadcasted_iota(jnp.int32, (6, 1), 0)
    one6 = (i6 < 3).astype(eps.dtype)
    sig_ref[...] = sigma_dev + bulk * vol * one6
    i36 = jax.lax.broadcasted_iota(jnp.int32, (36, 1), 0)
    one36 = (((i36 // 6) < 3) & ((i36 % 6) < 3)).astype(eps.dtype)
    d_ref[...] = D_dev + bulk * one36

    # damping fraction: mean over springs of 1 − 1/(1+(γ_max/γr)^β)
    x = (gamma_max / gr) ** be
    wsum = jnp.maximum(jnp.sum(jnp.sign(jnp.abs(w))), 1.0)  # count weighted springs
    frac = jnp.sum(jnp.where(w > 0, 1.0 - 1.0 / (1.0 + x), 0.0), axis=0, keepdims=True) / wsum
    frac_ref[...] = frac

    ngrev_ref[...] = gamma_rev
    ntrev_ref[...] = tau_rev
    ngprev_ref[...] = gamma
    ngmax_ref[...] = gamma_max
    ndir_ref[...] = direction
    nvirg_ref[...] = virgin


@functools.partial(jax.jit, static_argnames=("tile_p", "interpret"))
def multispring_pallas(
    eps: jnp.ndarray,                 # [P,6]
    state: dict[str, jnp.ndarray],    # [P,S] each
    params,                           # SpringParams with [P] fields
    n: jnp.ndarray,                   # [S,6]
    w: jnp.ndarray,                   # [S]
    *,
    tile_p: int = 256,
    interpret: bool = True,
) -> tuple[jnp.ndarray, jnp.ndarray, dict[str, jnp.ndarray], jnp.ndarray]:
    """Returns (σ [P,6], D [P,6,6], new_state, frac [P]).

    The kernel sees every ``[P, …]`` array transposed (points on lanes).  A
    block spans all ``S`` springs, and the last block of a ``P`` that
    ``tile_p`` does not divide is ragged (its out-of-range lanes are never
    written back), so no padded copy of the state is made in HBM."""
    P, S = state["gamma_rev"].shape
    dt = eps.dtype
    n = n.astype(dt)
    nnT = (n[:, :, None] * n[:, None, :]).reshape(S, 36).T
    row = lambda a: a.astype(dt)[None, :]
    keys = ("gamma_rev", "tau_rev", "gamma_prev", "gamma_max", "direction", "virgin")
    stT = [state[k].astype(dt if k not in ("direction", "virgin") else jnp.int32).T
           for k in keys]
    args = [
        eps.astype(dt).T,
        *stT,
        row(params.G0),
        row(params.gamma_r),
        row(params.beta),
        row(params.bulk),
        n,
        n.T,
        nnT,
        w.astype(dt)[:, None],
    ]
    grid = (pl.cdiv(P, tile_p),)
    colspec = lambda r: pl.BlockSpec((r, tile_p), lambda i: (0, i))
    fullspec = lambda r, c: pl.BlockSpec((r, c), lambda i: (0, 0))
    in_specs = [
        colspec(6),
        *[colspec(S)] * 6,
        colspec(1), colspec(1), colspec(1), colspec(1),
        fullspec(S, 6), fullspec(6, S), fullspec(36, S), fullspec(S, 1),
    ]
    out_specs = [colspec(6), colspec(36), colspec(1), *[colspec(S)] * 6]
    out_shape = [
        jax.ShapeDtypeStruct((6, P), dt),
        jax.ShapeDtypeStruct((36, P), dt),
        jax.ShapeDtypeStruct((1, P), dt),
        *[jax.ShapeDtypeStruct((S, P), dt)] * 4,
        *[jax.ShapeDtypeStruct((S, P), jnp.int32)] * 2,
    ]
    outs = pl.pallas_call(
        _ms_kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret,
    )(*args)
    sig, Dflat, frac, *new = outs
    new_state = {k: a.T for k, a in zip(keys, new)}
    return sig.T, Dflat.T.reshape(P, 6, 6), new_state, frac[0]
