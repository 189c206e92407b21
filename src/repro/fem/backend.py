"""Kernel-backend dispatch: put the Pallas kernels on the production hot path.

The repo carries two implementations of each FEM hotspot — the pure-jnp
oracle (``fem/spmv.ebe_element_lanes``, ``fem/multispring.update``) and the
hand-tuned Pallas kernels (``kernels/ebe_matvec``, ``kernels/multispring``).
Until this module existed, only tests ever ran the Pallas side: every
production path constructed ``FemOperators(mesh, cfg)`` bare, which means
``element_kernel=None`` → the jnp oracle, on TPU as much as on CPU.

:func:`resolve` turns a backend *spec* into a concrete
:class:`KernelBackend`, and :func:`make_operators` is the production
constructor every driver (``methods.run``/``run_ensemble``, the campaign
runner, the autotuner probe, the CLI) now goes through:

``auto``
    compiled Pallas on TPU/GPU, the jnp oracle elsewhere — "fastest
    available" as a default.  On the CPU test container this resolves to
    jnp: interpret-mode Pallas is a correctness tool, not a fast path, so
    it is never chosen implicitly.
``pallas``
    Pallas, compiled where the platform can (TPU/GPU), *interpret mode*
    otherwise — the explicit request is what legitimizes the slow
    interpreter (CI uses exactly this to keep the dispatch wiring honest).
``jnp``
    the pure-jnp oracle everywhere.
``pallas_interpret``
    force interpret mode even on TPU/GPU (kernel debugging).

Per-kernel overrides (``SeismicConfig.ebe_backend`` / ``ms_backend``) pin
one kernel's backend independently of the global spec, and
``tile_e``/``tile_p`` are the Pallas tiling knobs threaded through to the
kernels.  The resolved backend is part of the campaign signature
(``campaign/runner._campaign_sig``), so a checkpoint records what produced
it and refuses to resume under a different backend.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

BACKEND_SPECS = ("auto", "jnp", "pallas", "pallas_interpret")
_RESOLVED = ("jnp", "pallas", "pallas_interpret")
_COMPILED_PLATFORMS = ("tpu", "gpu")


def _platform() -> str:
    import jax

    return jax.default_backend()


def resolve_spec(spec: str, platform: Optional[str] = None) -> str:
    """One spec → one resolved backend name (no ``auto`` left)."""
    if spec not in BACKEND_SPECS:
        raise ValueError(
            f"unknown kernel backend {spec!r}; one of {BACKEND_SPECS}"
        )
    platform = platform or _platform()
    if spec == "auto":
        return "pallas" if platform in _COMPILED_PLATFORMS else "jnp"
    if spec == "pallas":
        return "pallas" if platform in _COMPILED_PLATFORMS else "pallas_interpret"
    return spec


@dataclasses.dataclass(frozen=True)
class KernelBackend:
    """Resolved per-kernel backend choice + Pallas tiling knobs.

    ``ebe``/``multispring`` are fully resolved names (never ``auto``);
    :meth:`element_kernel`/:meth:`multispring_fn` return the callables
    ``FemOperators`` plugs in — ``None`` for the jnp oracle, matching the
    seed ``FemOperators(element_kernel=None)`` convention exactly.
    """

    ebe: str = "jnp"
    multispring: str = "jnp"
    tile_e: int = 512
    tile_p: int = 256

    def __post_init__(self):
        for field in ("ebe", "multispring"):
            v = getattr(self, field)
            if v not in _RESOLVED:
                raise ValueError(
                    f"KernelBackend.{field}={v!r} is not resolved; one of {_RESOLVED}"
                )
        if self.tile_e < 1 or self.tile_p < 1:
            raise ValueError(f"tile_e={self.tile_e}, tile_p={self.tile_p} must be ≥ 1")

    @property
    def name(self) -> str:
        """Collapsed label for logs: the common name, or ``mixed``."""
        return self.ebe if self.ebe == self.multispring else "mixed"

    def describe(self) -> str:
        """Stable identity string — folded into the campaign signature."""
        return (
            f"ebe={self.ebe},ms={self.multispring},"
            f"tile_e={self.tile_e},tile_p={self.tile_p}"
        )

    def element_kernel(self) -> Optional[Callable]:
        if self.ebe == "jnp":
            return None
        from repro.kernels.ebe_matvec import ops as ebe_ops

        return functools.partial(
            ebe_ops.element_kernel,
            tile_e=self.tile_e,
            interpret=self.ebe == "pallas_interpret",
        )

    def multispring_fn(self) -> Optional[Callable]:
        if self.multispring == "jnp":
            return None
        from repro.kernels.multispring import ops as ms_ops

        return functools.partial(
            ms_ops.update,
            tile_p=self.tile_p,
            interpret=self.multispring == "pallas_interpret",
        )


def resolve(cfg=None, *, platform: Optional[str] = None, backend: Optional[str] = None,
            ebe: Optional[str] = None, multispring: Optional[str] = None,
            tile_e: Optional[int] = None, tile_p: Optional[int] = None) -> KernelBackend:
    """Resolve a :class:`~repro.fem.methods.SeismicConfig`'s backend knobs
    (or explicit keyword overrides) into a :class:`KernelBackend`.

    Precedence per kernel: explicit keyword > per-kernel cfg override
    (``cfg.ebe_backend``/``cfg.ms_backend``, empty string = inherit) >
    global spec (``backend`` keyword or ``cfg.backend``) > ``"auto"``.
    ``platform`` overrides ``jax.default_backend()`` (tests exercise the
    TPU/GPU arms without the hardware).
    """
    base = backend or (getattr(cfg, "backend", None) or "auto")
    ebe_spec = ebe or (getattr(cfg, "ebe_backend", None) or base)
    ms_spec = multispring or (getattr(cfg, "ms_backend", None) or base)
    return KernelBackend(
        ebe=resolve_spec(ebe_spec, platform),
        multispring=resolve_spec(ms_spec, platform),
        tile_e=tile_e if tile_e is not None else getattr(cfg, "tile_e", 512),
        tile_p=tile_p if tile_p is not None else getattr(cfg, "tile_p", 256),
    )


def make_operators(mesh, cfg, *, element_kernel=None, multispring_fn=None,
                   platform: Optional[str] = None):
    """The production ``FemOperators`` constructor: resolve ``cfg``'s backend
    spec and wire the chosen kernels in.  Explicit ``element_kernel``/
    ``multispring_fn`` arguments still win (the test-injection hook), and the
    resolved :class:`KernelBackend` is attached as ``ops.kernel_backend`` so
    callers (the campaign signature, logs) can record what was chosen.
    """
    from repro.fem import methods

    kb = resolve(cfg, platform=platform)
    ops = methods.FemOperators(
        mesh, cfg,
        element_kernel=element_kernel if element_kernel is not None else kb.element_kernel(),
        multispring_fn=multispring_fn if multispring_fn is not None else kb.multispring_fn(),
    )
    ops.kernel_backend = kb
    return ops
