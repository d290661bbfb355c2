"""Backend set-up shared by the entry points (CLI, bench.py,
chip_smoke.py, scripts/).

- ``enable_compile_cache`` turns on JAX's persistent compilation cache
  before the first compile, so a second process on the chip skips it.
- ``check_backend`` stops a run meant for the chip when JAX found none:
  no entry point carries on on the host CPU unless it was asked to.
- ``JAX_PLATFORMS=cpu``, or ``--cpu`` on a script (``add_cpu_arg``),
  is how a run asks for the host CPU; ``use_host_devices`` then splits
  the host into virtual devices for the mesh code.
"""

from __future__ import annotations

import os

#: Repo root (this file lives at p2p_gossip_tpu/utils/platform.py).
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

#: Compile-cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset.
#: A fixed path: it is part of the cache key, so a moving one never hits.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def cpu_requested() -> bool:
    """True when the user pinned jax to the host CPU via env."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu"


def compile_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else the in-repo default."""
    return (
        os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or DEFAULT_COMPILE_CACHE_DIR
    )


def enable_compile_cache() -> str | None:
    """Point JAX's persistent compilation cache at ``compile_cache_dir()``;
    call before the first compile. JAX reads ``JAX_COMPILATION_CACHE_DIR``
    itself, so when it is set nothing is configured here. A run pinned to
    the CPU (tests, rehearsals) leaves the cache off and returns None, so
    no test writes compiled programs into the checkout."""
    if cpu_requested():
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return compile_cache_dir()


def use_host_devices(n: int) -> None:
    """Under ``JAX_PLATFORMS=cpu``, ask XLA for ``n`` virtual host devices
    so mesh code runs without a chip. Must run before JAX initializes its
    backend; an existing device-count flag wins."""
    if not cpu_requested():
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}"
        ).strip()


def check_backend() -> str:
    """The platform JAX runs on. Exits non-zero when JAX found no TPU and
    the CPU was not asked for: a measurement meant for the chip never
    carries on elsewhere under a chip's name."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and not cpu_requested():
        raise SystemExit(
            f"no TPU found (JAX runs on {platform!r}); set JAX_PLATFORMS=cpu "
            "or pass --cpu to run on the host CPU"
        )
    return platform


def add_cpu_arg(ap) -> None:
    """Attach the standard ``--cpu`` flag to a script's argparse. Call
    :func:`apply_cpu_arg` right after ``parse_args``."""
    ap.add_argument(
        "--cpu", action="store_true",
        help="run on the host CPU (same as JAX_PLATFORMS=cpu); results "
        "are labeled so they are never mistaken for on-chip numbers",
    )


def apply_cpu_arg(args) -> None:
    """Honor ``--cpu`` before the first device query."""
    if getattr(args, "cpu", False):
        os.environ["JAX_PLATFORMS"] = "cpu"
