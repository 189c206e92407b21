"""Pre-jax-import bootstrap + multi-host ``jax.distributed`` bring-up.

Forcing N virtual host devices must happen before jax initializes its
backends, so every launcher parses its device flag *before* ``import jax``.
This helper is the single implementation (launch/train.py,
launch/campaign.py, examples/ensemble_surrogate.py and
benchmarks/campaign_bench.py all bootstrap through it) — it must therefore
never import jax at module level; :func:`distributed_init` imports it
lazily, which is safe because callers invoke it before any device is
touched (backend initialization, not the import, is the point of no
return).

Multi-host launchers bootstrap in two stages:

1. :func:`parse_distributed` — before ``import jax``: reads the
   ``--coordinator`` / ``--num-processes`` / ``--process-id`` /
   ``--cpu-backend`` flags and sets the pre-backend environment
   (``JAX_PLATFORMS=cpu`` for the CPU override the multi-process tests
   use, plus :func:`force_host_devices` for virtual host devices).
2. :func:`distributed_init` — after ``import jax`` but before first device
   use: calls ``jax.distributed.initialize`` so every process sees the
   global device set and the coordination service is up for barriers
   (``repro.parallel.distributed``).
"""
from __future__ import annotations

import argparse
import dataclasses
import os

_FORCE_FLAG = "--xla_force_host_platform_device_count"
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/bootstrap.py → the checkout root
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
# what enable_compile_cache sets so that the key holds op names, not locations
CACHE_KEY_CONFIG = {"jax_compilation_cache_include_metadata_in_key": True,
                    "jax_traceback_in_locations_limit": 0}


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on for this process and
    return its directory: the one ``JAX_COMPILATION_CACHE_DIR`` names when
    it is set (JAX reads the variable itself; no other directory is set),
    else a fixed ``.jax_cache/`` at the root of the checkout — fixed,
    because the path is part of what the cache matches on.  Entry points
    call this; nothing does at import time.

    The key holds each instruction's op name, the path of named scopes
    that a device profile reads, and no source location.  JAX's default key
    leaves all metadata out, so two builds that differ only in their scopes
    would share entries, and an executable loaded from the cache keeps the
    op names of the build that compiled it: a profile would name layers the
    code no longer has.  The programs carry no file, line or caller stack,
    so a moved line, another launcher or another install path still finds
    the entry."""
    import jax  # noqa: PLC0415 (deliberate lazy import, see module docstring)

    for name, value in CACHE_KEY_CONFIG.items():
        jax.config.update(name, value)
    if os.environ.get(_CACHE_ENV):
        return os.environ[_CACHE_ENV]
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def force_host_devices(flag: str = "--host-devices", default: int = 0) -> int:
    """Parse ``flag`` from ``sys.argv`` and force that many virtual host
    devices via ``XLA_FLAGS``.  Call before the first ``import jax``.

    A count already present in ``XLA_FLAGS`` (e.g. set by CI or a test
    harness) wins — appending a second, conflicting
    ``--xla_force_host_platform_device_count`` would be undefined.
    Returns the requested count (0 = not requested).
    """
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument(flag, type=int, default=default, dest="n")
    args, _ = ap.parse_known_args()
    if args.n and _FORCE_FLAG not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + f" {_FORCE_FLAG}={args.n}"
        )
    return args.n


@dataclasses.dataclass(frozen=True)
class DistributedArgs:
    """Parsed multi-host topology (``num_processes == 1`` → single-host)."""

    coordinator: str | None = None  # "host:port" of process 0's service
    num_processes: int = 1
    process_id: int = 0
    cpu_backend: bool = False       # force JAX_PLATFORMS=cpu (test rehearsal)

    def __post_init__(self):
        if self.num_processes < 1:
            raise ValueError(f"num_processes must be ≥ 1, got {self.num_processes}")
        if not 0 <= self.process_id < self.num_processes:
            raise ValueError(
                f"process_id {self.process_id} outside [0, {self.num_processes})"
            )
        if self.num_processes > 1 and not self.coordinator:
            raise ValueError("num_processes > 1 requires a coordinator host:port")

    @property
    def distributed(self) -> bool:
        return self.num_processes > 1


def parse_distributed(argv=None) -> DistributedArgs:
    """Parse the multi-host flags and set the pre-backend environment.

    Call before the first ``import jax`` (the ``--cpu-backend`` override
    works via ``JAX_PLATFORMS``, which the backend reads at initialization).
    Unknown flags are left for the launcher's own parser.
    """
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--coordinator", default=None,
                    help="process 0's coordination address, host:port")
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    ap.add_argument("--cpu-backend", action="store_true",
                    help="force the CPU backend (multi-process rehearsal)")
    args, _ = ap.parse_known_args(argv)
    if args.cpu_backend:
        os.environ["JAX_PLATFORMS"] = "cpu"
    return DistributedArgs(
        coordinator=args.coordinator, num_processes=args.num_processes,
        process_id=args.process_id, cpu_backend=args.cpu_backend,
    )


def distributed_init(dist: DistributedArgs | None = None, **overrides) -> "DistributedArgs":
    """Bring up ``jax.distributed`` for a multi-process launch.

    ``dist`` defaults to :func:`parse_distributed` over ``sys.argv``;
    keyword overrides (``coordinator=…, num_processes=…, process_id=…``)
    build the config programmatically — the path the subprocess test
    harness and ``benchmarks/campaign_bench.py --processes N`` use.  A
    single-process config is a no-op, so launchers call this
    unconditionally.  Must run before the first device use; jax is imported
    lazily to honor this module's pre-import contract.
    """
    if dist is None:
        # keyword-only use builds the topology from scratch — the caller's
        # argv may carry unrelated flags that must not be misparsed here
        dist = DistributedArgs() if overrides else parse_distributed()
    if overrides:
        dist = dataclasses.replace(dist, **overrides)
    if dist.cpu_backend:
        # effective only before backend initialization — the CLI path sets
        # this pre-import via parse_distributed; repeated here for
        # programmatic configs built after import but before device use
        os.environ["JAX_PLATFORMS"] = "cpu"
    if dist.distributed:
        import jax  # noqa: PLC0415 (deliberate lazy import, see docstring)

        jax.distributed.initialize(
            coordinator_address=dist.coordinator,
            num_processes=dist.num_processes,
            process_id=dist.process_id,
        )
    return dist
