"""Compile the main path's Pallas kernels and tuning programs for a TPU
v5e that is described, not attached: what the chip's compiler refuses
fails here, at no chip time. Nothing runs, so nothing here measures speed.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and under xdist
every worker imports every test file. Keep these tests in this one file.
"""
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import AxisType, Mesh, NamedSharding, SingleDeviceSharding

from repro.config import InputShape, TuneConfig
from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.mla_decode import mla_decode
from repro.kernels.rwkv_wkv import rwkv6_wkv
from repro.kernels.score_ce import score_ce
from repro.launch.mesh import data_axes
from repro.launch.steps import input_specs, make_train_step, step_shardings
from repro.models import build_model
from repro.train.remat import SAVE_LADDER
from repro.tuning import PromptTuner

HBM_BYTES = 16 * 2**30          # one v5e chip
TOKENS = 257                     # chip_smoke's task sequences (128 + 128 + 1)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to a persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:      # no TPU compiler on this host
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree.map(lambda s: _spec(s.shape, s.dtype, sharding), tree)


def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_flash_decode_compiles_at_qwen2_7b(one_chip):
    cfg = get_config("qwen2-7b")
    H, Hkv, hd = cfg.num_heads, cfg.kv_heads(), cfg.resolved_head_dim()
    assert (Hkv, H // Hkv, hd) == (4, 7, 128)
    B, L = 8, 4096
    _compile_kernel(
        lambda q, k, v: flash_decode(q, k, v, kv_len=L - 3),
        _spec((B, H, hd), jnp.bfloat16, one_chip),
        _spec((B, Hkv, L, hd), jnp.bfloat16, one_chip),
        _spec((B, Hkv, L, hd), jnp.bfloat16, one_chip))


def test_mla_decode_compiles_at_deepseek_v2(one_chip):
    cfg = get_config("deepseek-v2-236b")
    m, H = cfg.mla, cfg.num_heads
    assert (H, m.kv_lora_rank, m.qk_rope_head_dim) == (128, 512, 64)
    B, L = 4, 4096
    scale = 1.0 / (m.qk_nope_head_dim + m.qk_rope_head_dim) ** 0.5
    _compile_kernel(
        lambda ql, qp, c, k: mla_decode(ql, qp, c, k, scale=scale),
        _spec((B, H, m.kv_lora_rank), jnp.bfloat16, one_chip),
        _spec((B, H, m.qk_rope_head_dim), jnp.bfloat16, one_chip),
        _spec((B, L, m.kv_lora_rank), jnp.bfloat16, one_chip),
        _spec((B, L, m.qk_rope_head_dim), jnp.bfloat16, one_chip))


def test_flash_attention_compiles_at_gpt2_large(one_chip):
    cfg = get_config("gpt2-large")
    H, hd = cfg.num_heads, cfg.resolved_head_dim()
    assert (H, hd) == (20, 64)
    qkv = _spec((2, H, cfg.max_seq_len, hd), jnp.bfloat16, one_chip)
    _compile_kernel(flash_attention, qkv, qkv, qkv)


def test_score_ce_compiles_at_gpt2_large_vocab(one_chip):
    cfg = get_config("gpt2-large")
    assert cfg.vocab_size % 512            # odd vocab: padded + masked tail
    T = 16 * TOKENS
    _compile_kernel(
        score_ce,
        _spec((T, cfg.d_model), jnp.bfloat16, one_chip),
        _spec((cfg.vocab_size, cfg.d_model), jnp.bfloat16, one_chip),
        _spec((T,), jnp.int32, one_chip))


@pytest.mark.xfail(
    strict=True, raises=NotImplementedError,
    reason="Mosaic has no cumsum: 'Unimplemented primitive in Pallas TPU "
           "lowering for KernelType.TC: cumsum'")
def test_rwkv6_wkv_compiles_at_rwkv6_7b(one_chip):
    cfg = get_config("rwkv6-7b")
    hd = cfg.ssm.state_size
    BH, T = cfg.d_model // hd, 1024
    assert hd == 64
    x = _spec((BH, T, hd), jnp.bfloat16, one_chip)
    _compile_kernel(rwkv6_wkv, x, x, x, x,
                    _spec((BH, hd), jnp.float32, one_chip),
                    _spec((BH, hd, hd), jnp.float32, one_chip))


def _gpt2_large_tuner():
    cfg = get_config("gpt2-large")
    model = build_model(cfg)
    tune_cfg = TuneConfig(prompt_len=16, batch_size=16, eval_samples=16)
    return model, tune_cfg, PromptTuner(model, tune_cfg)


def _batch_specs(batch, sharding):
    return {"tokens": _spec((batch, TOKENS), jnp.int32, sharding),
            "labels": _spec((batch, TOKENS), jnp.int32, sharding),
            "mask": _spec((batch, TOKENS), jnp.float32, sharding)}


@pytest.mark.parametrize("program", ["step", "score"])
def test_prompt_tuner_program_fits_one_chip(one_chip, program):
    """GPT2-Large at published widths, batch 16 x 257 tokens + 16 prompt
    positions: what chip_smoke.py runs on one chip."""
    model, tune_cfg, tuner = _gpt2_large_tuner()
    params = _on(model.abstract_params(), one_chip)
    pp = {"soft_prompt": _spec((tune_cfg.prompt_len, model.cfg.d_model),
                               jnp.float32, one_chip)}
    batch = _batch_specs(tune_cfg.batch_size, one_chip)
    if program == "step":
        opt = _on(jax.eval_shape(tuner.init_opt, pp), one_chip)
        lowered = tuner._step.lower(pp, opt, params, batch)
    else:
        lowered = tuner._score.lower(pp, params, batch)
    mem = lowered.compile().memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes > 1.5e9      # bf16 weights, 774M
    assert used < HBM_BYTES, used


def _step_args(model, tune_cfg, tuner, tokens, sharding):
    pp = {"soft_prompt": _spec((tune_cfg.prompt_len, model.cfg.d_model),
                               jnp.float32, sharding)}
    batch = {k: _spec((tune_cfg.batch_size, tokens), d, sharding)
             for k, d in (("tokens", jnp.int32), ("labels", jnp.int32),
                          ("mask", jnp.float32))}
    return (pp, _on(jax.eval_shape(tuner.init_opt, pp), sharding),
            _on(model.abstract_params(), sharding), batch)


def _rung(program):
    """The save-ladder rung (0-based) a ``GradProgram`` compiled on."""
    chosen, = program._chosen.values()
    rung, = [r for r, j in program._rung_jits.items() if j is chosen]
    return rung


def _rematted_dots_of_width(text, width):
    """Instructions of the compiled text that recompute a dot in the
    backward pass with an output dimension ``width``."""
    return [line for line in text.splitlines()
            if "rematted_computation" in line and "dot_general" in line
            and re.search(rf"[\[,]{width}\]", line.split("metadata=")[0])]


def test_tuning_step_keeps_projections_at_qwen2_stage(one_chip):
    """The ``qwen2.tune`` cell's step (one 14-layer stage of Qwen2-7B,
    batch 8 x (16 + 257)): the first rung fits, so the backward pass reads
    q, k, v, gate and up instead of recomputing them."""
    cfg = get_config("qwen2-7b").with_overrides(num_layers=14)
    model = build_model(cfg)
    tune_cfg = TuneConfig(prompt_len=16, batch_size=8)
    tuner = PromptTuner(model, tune_cfg)
    compiled = tuner._step.lower(
        *_step_args(model, tune_cfg, tuner, TOKENS, one_chip)).compile()
    assert _rung(tuner._step) == 0
    assert not _rematted_dots_of_width(compiled.as_text(), cfg.d_ff)
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < HBM_BYTES)


def test_tuning_step_steps_down_at_gpt2_large_long(one_chip):
    """GPT2-Large at batch 16 x (16 + 1008): keeping all five outputs
    runs out of the chip's memory, so the step compiles a rung lower, and
    a fresh tuner (the next job) starts on that rung."""
    model, _, _ = _gpt2_large_tuner()
    tune_cfg = TuneConfig(prompt_len=16, batch_size=16)
    tuner = PromptTuner(model, tune_cfg)
    args = _step_args(model, tune_cfg, tuner, 1008, one_chip)
    tuner._step.lower(*args).compile()
    rung = _rung(tuner._step)
    assert rung > 0 and sorted(tuner._step._rung_jits) == list(
        range(rung + 1))
    fresh = PromptTuner(model, tune_cfg)
    fresh._step.lower(*args)
    assert list(fresh._step._rung_jits) == [rung]


def test_data_parallel_train_step_compiles_on_four_chips(topo):
    """chip_smoke.py --chips 4: global batch 64 over a 4-way data mesh;
    the prompt gradient must be all-reduced."""
    mesh = Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    cfg = get_config("gpt2-large")
    tune_cfg = TuneConfig(prompt_len=16)
    shape = InputShape("dp_smoke", TOKENS, 64, "train")
    model = build_model(cfg, model_axis=1, data_axis=4, mesh=mesh)
    specs = input_specs(model, shape, tune_cfg)
    sh = step_shardings(model, shape, mesh, specs)
    args = [jax.tree.map(lambda s, n: _spec(s.shape, s.dtype, n),
                         specs[k], sh[k])
            for k in ("params", "prompt_params", "opt_state", "batch")]
    assert args[3]["tokens"].sharding.spec[0] == "data"
    fn, _ = make_train_step(model, tune_cfg, batch_axes=data_axes(mesh))
    compiled = fn.lower(*args).compile()
    assert "all-reduce" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < HBM_BYTES)
