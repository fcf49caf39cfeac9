"""Main-path programs compiled for a described TPU v5e, without a chip.

The TPU compiler is installed with jax and compiles for a topology that
is described rather than attached. That catches what interpret mode and
the CPU backend cannot: dtypes the chip has no unit for, kernel tilings
Mosaic refuses, programs that do not fit the device. Nothing runs, so
these tests say nothing about results or times.

The topology is described inside a module fixture (never at import: only
one process may load the TPU library, and every test worker imports this
file). Where it cannot be described, the tests skip from the fixture.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.warpsim import _pallas, machines
from repro.core.warpsim.divergence import expand_stream
from repro.core.warpsim.trace import get_workload
from repro.kernels import moe_gmm
from repro.models import model as M


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described chip's executables cannot be read back without the
    # chip: keep them out of the persistent cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _structs(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


@pytest.fixture(scope="module")
def nqu_family_hlo(one_chip):
    """Optimized HLO of the paper grid's NQU family (6 machines, 8 padded
    units in one launch), compiled once for the described chip."""
    wl = get_workload("NQU")
    units = [(_pallas._stream_cols(expand_stream(wl, cfg)),
              _pallas._cfg_scalars(cfg))
             for cfg in machines.paper_suite().values()]
    dims, stacked = _pallas.pack_units(units)
    with jax.enable_x64(True):
        compiled = _pallas._get_launch(*dims).lower(
            _structs(stacked, one_chip)).compile()
    return compiled.as_text()


def test_warpsim_family_program_compiles_integer_only(nqu_family_hlo):
    """The NQU family compiles for the chip with no 64-bit float
    anywhere: times travel as int64 bit patterns, since XLA:TPU's f64 is
    not IEEE."""
    types = set(re.findall(r"\b([fsu]\d+)\[", nqu_family_hlo))
    assert "s64" in types
    assert not {"f64", "f32", "f16", "bf16"} & types, types
    assert "tpu_custom_call" not in nqu_family_hlo   # no kernel on this path


def test_warpsim_family_program_has_only_its_two_loops(nqu_family_hlo):
    """The family program's only loops are the outer step and the inner
    trip. A read of loop-carried state that XLA:TPU emits as a serial
    loop over the launch's units (a vmapped ``dynamic_slice`` of an L1
    row does) costs each inner trip one nested loop per u32 half."""
    loops = re.findall(r"\swhile\(", nqu_family_hlo)
    assert len(loops) == 2, len(loops)


def test_family_program_keeps_the_name_the_benchmark_reads():
    """``engine.warp_events_per_device_s`` finds the family program in
    the device trace by its module name. Lowered here on the CPU, the
    program has to carry that name, so that a rename fails this test
    instead of leaving the metric with nothing to read."""
    import ast

    reader = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "metrics",
        "engine.warp_events_per_device_s.py")
    with open(reader) as f:
        program = next(
            ast.literal_eval(node.value) for node in ast.parse(f.read()).body
            if isinstance(node, ast.Assign)
            and [t.id for t in node.targets] == ["PROGRAM"])
    cfg = machines.baseline(32)
    units = [(_pallas._stream_cols(expand_stream(
        get_workload("NQU", n_threads=64), cfg)), _pallas._cfg_scalars(cfg))]
    dims, stacked = _pallas.pack_units(units)
    with jax.enable_x64(True):
        text = _pallas._get_launch(*dims).lower(stacked).as_text()
    assert re.search(r"^module @(\S+)", text, re.M).group(1) == program
    assert program == "jit__simulate_one"


def test_tinyllama_decode_step_compiles_full_width(one_chip):
    """One fused decode step of tinyllama-1.1b at its published width,
    4 serving slots, fits one chip."""
    cfg = get_config("tinyllama-1.1b")
    params = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0),
                                                  cfg))
    cache = jax.eval_shape(lambda: M.init_decode_cache(cfg, 4, 64))
    compiled = jax.jit(lambda p, t, c: M.decode_step(p, cfg, t, c)).lower(
        _structs(params, one_chip),
        jax.ShapeDtypeStruct((4, 1), jnp.int32, sharding=one_chip),
        _structs(cache, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    logits, new_cache = compiled.out_info
    assert logits.shape == (4, cfg.vocab_padded)
    assert new_cache["kv"]["index"].shape == (4,)


def test_moe_gmm_kernel_compiles_qwen2_moe_widths(one_chip):
    """The grouped-matmul Pallas kernel lowers through Mosaic at
    qwen2-moe-a2.7b's expert widths (d_model 2048, 64 padded experts,
    expert FF 1408)."""
    cfg = get_config("qwen2-moe-a2.7b")
    m = 2048
    x = jax.ShapeDtypeStruct((m, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct(
        (cfg.moe_experts_eff, cfg.d_model, cfg.moe_d_ff), jnp.bfloat16,
        sharding=one_chip)
    block_expert = jax.ShapeDtypeStruct((m // 128,), jnp.int32,
                                        sharding=one_chip)
    compiled = moe_gmm.gmm.lower(x, w, block_expert,
                                 interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (m, cfg.moe_d_ff)
    assert np.dtype(compiled.out_info.dtype) == np.dtype(jnp.bfloat16)
