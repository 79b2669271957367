"""Compile rehearsals for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode and the CPU accept: blocks not
aligned to the (8, 128) tiling, kernels that use too much fast memory,
programs that do not fit.  These tests compile the main path's kernels at
real widths and one pipeline step at GPT-XL widths for ``v5e:2x2``, so a PR
that breaks them fails here instead of on the chip.  Nothing runs.

Only one process at a time may load the TPU library, so the topology is
described inside a module fixture (never at import): every xdist worker
collects the same tests, and only the worker given this file loads the
library.  Keep these tests in this one file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.gpt import GPT_CONFIGS
from repro.configs.mamba2_780m import FULL as MAMBA2_780M
from repro.core.schedule import make_plan
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.ssd_scan.kernel import ssd_chunked_pallas
from repro.optim import make_optimizer
from repro.pipeline.engine import make_pipeline_step
from repro.pipeline.stage import StagedModel
from repro.training import TrainState, create_train_state


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def stage_mesh4(topo):
    from jax.sharding import AxisType, Mesh

    return Mesh(np.array(topo.devices).reshape(4), ("stage",), axis_types=(AxisType.Auto,))


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize(
    "arch,dtype",
    [("GPT-Medium", jnp.bfloat16), ("GPT-Large", jnp.float32), ("GPT-XL", jnp.bfloat16)],
)
def test_flash_attention_compiles_for_v5e(arch, dtype, one_chip, no_persistent_cache):
    cfg = GPT_CONFIGS[arch]
    q = jax.ShapeDtypeStruct((cfg.num_heads, 1024, cfg.hd), dtype, sharding=one_chip)
    compiled = (
        jax.jit(lambda q, k, v: flash_attention_pallas(q, k, v, causal=True))
        .lower(q, q, q)
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles_for_v5e_at_mamba2_780m_widths(one_chip, no_persistent_cache):
    cfg = MAMBA2_780M
    H, P, N, T = cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, 2048
    assert (H, P, N) == (48, 64, 128)

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (
        sd((1, T, H, P), cfg.dtype),
        sd((1, T, H), jnp.float32),
        sd((H,), jnp.float32),
        sd((1, T, N), cfg.dtype),
        sd((1, T, N), cfg.dtype),
    )
    compiled = (
        jax.jit(lambda *a: ssd_chunked_pallas(*a, chunk=cfg.ssm_chunk)).lower(*args).compile()
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_spmd_pipeline_step_compiles_for_v5e_2x2_at_gpt_xl_widths(stage_mesh4, no_persistent_cache):
    """One 1F1B training step (engine + AdamW, state donated) of GPT-XL with
    one layer per stage, stage-sharded over the four described chips."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    S, M, b, T = 4, 4, 1, 1024
    cfg = GPT_CONFIGS["GPT-XL"].replace(num_layers=S)
    staged = StagedModel.build(cfg, S)
    opt = make_optimizer("adamw", schedule=lambda s: jnp.float32(1e-4))
    stage = NamedSharding(stage_mesh4, P("stage"))
    rep = NamedSharding(stage_mesh4, P())
    state = jax.eval_shape(
        lambda: create_train_state(staged.init_all_stages(jax.random.PRNGKey(0)), opt)
    )
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=stage if x.ndim and x.shape[0] == S else rep
        ),
        state,
    )
    tokens = jax.ShapeDtypeStruct((M, b, T), jnp.int32, sharding=rep)
    engine = make_pipeline_step(staged, make_plan(S, M, 1, micro_batch_size=b), stage_mesh4)

    def step(state, tokens, labels):
        loss, grads = engine(state.params, tokens, labels)
        params, opt_state, _ = opt.update(state.params, grads, state.opt_state)
        return TrainState(state.step + 1, params, opt_state), loss

    compiled = jax.jit(step, donate_argnums=0).lower(state, tokens, tokens).compile()
    text = compiled.as_text()
    assert "collective-permute" in text  # the stage-to-stage transfers
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 0  # the donated state is reused in place
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 * 2**30
