"""Process-level JAX set-up shared by the entry points.

``chip_smoke.py``, ``examples/poker_dvs_serve.py``, ``examples/sharded_serve.py``
and ``bench/`` call these before their first computation.
Importing this module does not import JAX: :func:`fake_host_devices` has to
run before JAX starts its backend.
"""

from __future__ import annotations

import os
import pathlib
import sys

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: JAX reads
    it and nothing is set here. Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache`` (git-ignored), so a second run of any entry
    point from the same checkout finds the first run's programs.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def fake_host_devices(n: int) -> None:
    """Split the CPU backend into ``n`` devices (the ``--devices`` flags).

    Must run before JAX is imported. The flag only shapes the CPU backend,
    so on an accelerator it would silently leave every shard on one chip:
    refuse instead.
    """
    if "jax" in sys.modules:
        raise SystemExit("--devices must take effect before jax is imported")
    flags = os.environ.get("XLA_FLAGS", "")
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}"
    ).strip()
    import jax

    if jax.default_backend() != "cpu":
        raise SystemExit(
            f"--devices fakes CPU host devices, but the backend is "
            f"{jax.default_backend()!r}; run without --devices to use the "
            "real devices"
        )
