"""Compile the main path for a described TPU v5e chip, without the chip.

The TPU compiler is installed even where no chip is attached: a topology
can be *described* and programs lowered and compiled against its devices.
That refuses what interpret mode and the CPU backend accept — Pallas
tilings, memory that does not fit, ops the chip lacks — at no chip time.
Nothing runs here, so these tests say nothing about results or speed.

Only one process may load the TPU library, so the topology is described
inside a fixture (never at import) and every test of that kind lives in
this one file.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.core import engine
from repro.core.accelerator import CATALOG
from repro.core.controller import FleetController
from repro.core.flow import SLO, FlowSpec, Path, TrafficPattern
from repro.core.interconnect import LinkSpec, mem_bw
from repro.core.profiler import ProfileTable
from repro.core.runtime import ArcusRuntime
from repro.core.token_bucket import TBState
from repro.kernels.token_bucket import ops as tb_ops
from repro.models import transformer as T

#: one v5e chip's HBM
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # else the TPU library writes its logs outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


# ---------------------------------------------------------------------------
# The batched fleet engine (one window of FleetController.run)
# ---------------------------------------------------------------------------

_COMPLEMENTS = (["synthetic50"], ["synthetic50", "aes256"],
                ["synthetic50", "aes256", "ipsec32"])


def _specs(b: int) -> list[FlowSpec]:
    return [FlowSpec(i, i, Path.FUNCTION_CALL, i,
                     TrafficPattern(1024 if i == 0 else 512 << (i % 2),
                                    load=0.3, process="poisson"),
                     SLO.gbps(2.0))
            for i in range(len(_COMPLEMENTS[b % 3]))]


class _Captured(Exception):
    pass


def _capture_window(monkeypatch, link, sim_kwargs):
    """The compiled engine function and arguments of the first window of a
    B=8 fleet run (admission profiling runs on the CPU beforehand)."""
    profile = ProfileTable(link, n_ticks=1_000)
    rts = [ArcusRuntime([CATALOG[n] for n in _COMPLEMENTS[b % 3]],
                        link=link, profile_table=profile)
           for b in range(8)]
    ctrl = FleetController(rts)
    assert all(all(a) for a in ctrl.admit_fleet([_specs(b)
                                                  for b in range(8)]))

    def get_run(_key, builder):
        fn = builder()

        def run(carry, args):
            raise _Captured(fn, carry, args)
        return run

    monkeypatch.setattr(engine, "_get_run", get_run)
    with pytest.raises(_Captured) as cap:
        ctrl.run(total_ticks=3_000, window_ticks=1_500, seeds=list(range(8)),
                 sim_kwargs=sim_kwargs)
    return cap.value.args


_DOT = re.compile(r"stablehlo\.dot_general.*")


@pytest.mark.parametrize("variant", ["fleet_slo", "every_vector_stage"])
def test_fleet_engine_window_compiles(variant, one_chip, monkeypatch):
    """The engine's fleet window compiles for one v5e chip, and none of its
    float32 matmuls runs at DEFAULT precision (a single bf16 pass on the
    TPU, which would round the grant stage's byte prefix sums).
    ``every_vector_stage`` adds a resource axis and lowers the service
    vectorization threshold so every matmul the engine has is present."""
    if variant == "fleet_slo":
        link, sim_kwargs = None, None
    else:
        link, sim_kwargs = (LinkSpec(resources=(mem_bw(40.0),)),
                            {"service_vec_min": 1})
    fn, carry, args = _capture_window(monkeypatch, link, sim_kwargs)
    lowered = fn.lower(_shapes(carry, one_chip), _shapes(args, one_chip))
    dots = _DOT.findall(lowered.as_text())
    assert dots, "expected the grant stage's prefix-sum matmuls"
    f32_default = [d for d in dots
                   if "xf32>" in d and "HIGHEST" not in d]
    assert not f32_default, f32_default
    mem = lowered.compile().memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES


# ---------------------------------------------------------------------------
# The token-bucket Pallas kernel, compiled (not interpreted)
# ---------------------------------------------------------------------------


def test_token_bucket_kernel_compiles(one_chip):
    n = 4096
    i32 = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    state = TBState(*[i32] * 6)
    step = jax.jit(functools.partial(tb_ops.token_bucket_step,
                                     interpret=False))
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    want = jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip)
    compiled = step.lower(state, scalar, i32, want).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# The serving model at gemma3-12b full width, cut to 6 layers
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def gemma3_6l():
    cfg = dataclasses.replace(get_config("gemma3-12b"), n_layers=6)
    params = jax.eval_shape(lambda: T.init_model_params_only(0, cfg))
    return cfg, params


def test_gemma3_param_init_fits_one_chip(gemma3_6l, one_chip):
    """bf16 parameters are drawn and cast in one program: its peak stays
    near the bf16 tree, not the float32 one (which alone is ~9.4 GB)."""
    cfg, params = gemma3_6l
    init = jax.jit(lambda: T.init_model_params_only(0, cfg),
                   out_shardings=one_chip)
    mem = init.lower().compile().memory_analysis()
    bf16 = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert bf16 <= mem.output_size_in_bytes < 1.01 * bf16   # tile padding
    assert mem.output_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES // 2


def test_gemma3_decode_step_compiles(gemma3_6l, one_chip):
    cfg, params = gemma3_6l
    batch, max_len = 4, 128
    cache = jax.eval_shape(lambda: T.init_cache(cfg, batch, max_len,
                                                jnp.bfloat16))
    tokens = jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=one_chip)
    step = jax.jit(T.decode_step, static_argnums=(1,))
    compiled = step.lower(_shapes(params, one_chip), cfg, tokens, lengths,
                          _shapes(cache, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 4 * 10**9     # the full-width model
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < V5E_HBM_BYTES
