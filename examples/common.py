"""Shared bootstrap for the examples (the launch-env role of the
reference's scripts/launch.sh: device/world setup before any framework
import). Call `bootstrap()` FIRST — before importing jax anywhere else —
so the virtual CPU mesh is in place when no multi-chip TPU slice is
attached. With `--tpu` (or on a real multi-chip slice) the examples run
natively."""

import os
import sys

# runnable from anywhere: the repo root is the package root
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def bootstrap(world: int = 4):
    """Returns (jax, mesh) with >= `world` devices on the chosen backend.

    Default: a virtual CPU mesh with spare devices (interpret-mode Pallas
    simulates the inter-chip DMA; see tests/conftest.py for why spares
    matter). `--tpu` uses the real TPU devices and fails when there are
    fewer than `world` (pass `--world N` to ask for what is there);
    `--world N` overrides the mesh size.
    """
    if "--world" in sys.argv:
        i = sys.argv.index("--world")
        try:
            world = int(sys.argv[i + 1])
        except (IndexError, ValueError):
            raise SystemExit("--world requires an integer value")
    use_tpu = "--tpu" in sys.argv
    if not use_tpu:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={world + 4}"
        )
    import jax

    if not use_tpu:
        jax.config.update("jax_platforms", "cpu")
    from triton_dist_tpu.runtime import enable_compile_cache, make_mesh

    enable_compile_cache()  # before the first compile
    if len(jax.devices()) < world:
        raise SystemExit(
            f"this example wants a {world}-device mesh and JAX found "
            f"{len(jax.devices())}: pass --world {len(jax.devices())}")
    return jax, make_mesh((world,), ("tp",))
