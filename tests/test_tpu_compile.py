"""Compile the main path's device programs for a described TPU v5e.

No chip is needed: the TPU compiler is installed, and compiles for a
topology that is described, not attached. A compile that passes is not a
chip run; it catches what interpret mode cannot — Mosaic lowering rules,
tile alignment, VMEM limits — at no chip time. x64 is off inside every
test, as on the chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _chip_config():
    """x64 off, and no persistent cache: an entry written for a described
    chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            yield
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()


def _f32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _compile(fn, *args):
    """The compiled TPU program's text (the compile raises what the chip's
    compiler would)."""
    return jax.jit(fn).lower(*args).compile().as_text()


def test_lu_sweep_compiles(one_chip):
    """The inline transport's fused (B, n, n) sweep at a gateway bucket."""
    from repro.api.transport import _lu_sweep

    _compile(lambda x: _lu_sweep(x, num_servers=4),
             _f32(one_chip, 8, 1024, 1024))


@pytest.mark.parametrize("growth_safe", [False, True])
@pytest.mark.parametrize("k", [0, 1, 2, 3])
def test_ced_compiles(one_chip, k, growth_safe):
    from repro.kernels.ced import ced

    hlo = _compile(
        lambda m, v: ced(m, v, k, growth_safe=growth_safe, interpret=False),
        _f32(one_chip, 1024, 1024), _f32(one_chip, 1024),
    )
    assert "tpu_custom_call" in hlo


def test_ced_pads_off_tile_n(one_chip):
    """n=1000 is not a multiple of the 128 tile: padded, still Mosaic."""
    from repro.kernels.ced import ced

    hlo = _compile(lambda m, v: ced(m, v, 1, interpret=False),
                   _f32(one_chip, 1000, 1000), _f32(one_chip, 1000))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kernel", ["lu_panel", "trsm_lower",
                                    "trsm_upper_right", "schur_update"])
def test_lu_kernels_compile(one_chip, kernel):
    from repro.kernels.gemm import schur_update
    from repro.kernels.lu_panel import lu_panel_compact
    from repro.kernels.trsm import trsm_lower, trsm_upper_right

    b = 256
    fn, shapes = {
        "lu_panel": (lambda x: lu_panel_compact(x, interpret=False),
                     [(b, b)]),
        "trsm_lower": (lambda l, x: trsm_lower(l, x, interpret=False),
                       [(b, b), (b, b)]),
        "trsm_upper_right": (
            lambda u, x: trsm_upper_right(u, x, interpret=False),
            [(b, b), (b, b)]),
        "schur_update": (
            lambda c, a, x: schur_update(c, a, x, interpret=False),
            [(1024, 1024)] * 3),
    }[kernel]
    hlo = _compile(fn, *(_f32(one_chip, *s) for s in shapes))
    assert "tpu_custom_call" in hlo


def test_relay_with_four_servers_per_chip_compiles(topo):
    """The shard_map relay of 16 servers over the described host's four
    chips (k = 4, b = 64 takes the blocked panel): one program, whose
    only collectives are the hops between chips."""
    from functools import partial

    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.distrib.spdc_pipeline import _server_program

    n, servers = 1024, 16
    mesh = Mesh(topo.devices, ("servers",))
    spec = P("servers", None)
    fn = jax.shard_map(
        partial(_server_program, n=n, b=n // servers, num_servers=servers,
                axis="servers"),
        mesh=mesh, in_specs=spec, out_specs=(spec, spec),
    )
    txt = _compile(fn, _f32(NamedSharding(mesh, spec), n, n))
    assert "collective-permute" in txt
    assert "all-gather" not in txt and "all-reduce" not in txt
