"""The FL path's Pallas kernels compile for a TPU v5e, at real widths.

Each case lowers one kernel with ``interpret=False`` for a chip that is
described, not attached (``jax.experimental.topologies``), and compiles it
with the TPU compiler — which refuses what interpret mode accepts: blocks
not aligned to the (8, 128) tile, scalar stores to vector memory, shape
casts Mosaic cannot lay out. Nothing runs, so these say nothing about
results or speed; ``chip_smoke.py`` checks results on the chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import common

CNN_PARAMS = 6_603_710           # configs/femnist_cnn.CONFIG
G, B = 10, 10 * 32               # M groups × L·n superbatch images


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _gbp_cs():
    from repro.kernels.gbp_cs import ops
    return (lambda a, x, y: ops.fused_step(a, x, y, interpret=False),
            [(62, 35), (35,), (62,)])


def _agg_weighted():
    from repro.kernels.agg_weighted import ops
    return (lambda s, w: ops.agg_flat(s, w, interpret=False),
            [(10, CNN_PARAMS), (10,)])


def _conv(hw, cin, cout):
    def build():
        from repro.kernels.conv_fused import ops
        return (lambda x, w, b: ops.conv_block_grouped(x, w, b,
                                                       interpret=False),
                [(G, B, hw, hw, cin), (G, 5, 5, cin, cout), (G, cout)])
    return build


def _robust(method):
    def build():
        from repro.kernels.robust_agg import kernel
        pp = common.pad_to(CNN_PARAMS, 512)
        return (lambda s, a: kernel.robust_agg_kernel(s, a, method=method,
                                                      interpret=False),
                [(10, pp), (10,)])
    return build


@pytest.mark.parametrize("build,name", [
    (_gbp_cs, "gbp_cs"), (_agg_weighted, "agg_weighted"),
    (_conv(28, 1, 32), "conv_fused"), (_conv(14, 32, 64), "conv_fused"),
    (_robust("coord_median"), "robust_agg"),
    (_robust("trimmed_mean"), "robust_agg"),
], ids=["gbp_cs", "agg_weighted", "conv_fused_layer1", "conv_fused_layer2",
        "robust_agg_coord_median", "robust_agg_trimmed_mean"])
def test_kernel_compiles_for_v5e(build, name, one_chip):
    fn, shapes = build()
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    # the kernel's name is its instruction's, and so its trace event's
    assert re.search(rf"%{name}(\.\d+)? = .*tpu_custom_call", text)


def test_topk_compress_refuses_sizes_past_the_chip_limit():
    """Past the largest P compiled within the compile-time budget, the
    compiled route raises instead of handing the compiler the whole vector."""
    from repro.kernels.topk_compress import ops
    p_max = int(common.COMPILED_MAX_ELEMS["topk_compress"] ** 0.5)
    with pytest.raises(ValueError, match="topk_compress.*ROADMAP S2"):
        ops.topk_select_flat(jnp.zeros((p_max + 1,), jnp.float32), 5,
                             interpret=False)
