"""Real-size compiles for one described TPU v5e chip — no chip attached.

The TPU compiler is installed with jaxlib's TPU support, and it compiles
for a chip that is only described (``topologies.get_topology_desc``). This
refuses, without chip time, what interpret mode cannot see: blocks that
break the (8, 128) tiling rule, primitives Mosaic cannot lower, kernels
that need more VMEM than a core has, programs larger than the device.

The topology is described inside a module fixture, never while a module
is imported: only one process may load the TPU library at a time, and
under pytest-xdist only the worker running this file may do so. All such
compiles live in this one file for the same reason.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it.
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    total = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert total < HBM_BYTES, f"{total} bytes do not fit one chip"
    return compiled


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows,width", [(4096, 1024), (512, 8192)])
def test_text_scan_compiles(one_chip, rows, width):
    from repro.kernels.text_clean.ops import text_scan_op

    compiled = _compile(
        lambda x: text_scan_op(x, lower=True, strip_html=True, strip_parens=True),
        _spec(one_chip, (rows, width), jnp.uint8),
    )
    assert _has_kernel(compiled)


def test_text_clean_compiles(one_chip):
    from repro.kernels.text_clean.ops import text_clean_op

    compiled = _compile(text_clean_op, _spec(one_chip, (4096, 512), jnp.uint8))
    assert _has_kernel(compiled)


def test_lstm_cell_compiles_at_summarizer_widths(one_chip):
    from repro.configs.p3sapp_summarizer import CONFIG
    from repro.kernels.lstm_cell.ops import lstm_cell_op

    b, d_in, hid = 32, CONFIG.d_embed, CONFIG.d_hidden
    params = {
        "wx": _spec(one_chip, (d_in, 4 * hid), jnp.float32),
        "wh": _spec(one_chip, (hid, 4 * hid), jnp.float32),
        "b": _spec(one_chip, (4 * hid,), jnp.float32),
    }
    compiled = _compile(
        lambda x, h, c, p: lstm_cell_op(x, h, c, p),
        _spec(one_chip, (b, d_in), jnp.float32),
        _spec(one_chip, (b, hid), jnp.float32),
        _spec(one_chip, (b, hid), jnp.float32),
        params,
    )
    assert _has_kernel(compiled)


def test_flash_attention_compiles_bf16(one_chip):
    from repro.kernels.flash_attention.ops import flash_attention_op

    qkv = _spec(one_chip, (1, 1024, 8, 64), jnp.bfloat16)
    compiled = _compile(
        lambda q, k, v: flash_attention_op(q, k, v, causal=True), qkv, qkv, qkv
    )
    assert _has_kernel(compiled)


def test_rg_lru_compiles(one_chip):
    from repro.kernels.rg_lru.ops import rg_lru_op

    seq = _spec(one_chip, (4, 2048, 1024), jnp.float32)
    compiled = _compile(rg_lru_op, seq, seq, _spec(one_chip, (4, 1024), jnp.float32))
    assert _has_kernel(compiled)


def test_mlstm_chunk_compiles(one_chip):
    from repro.kernels.mlstm_chunk.ops import mlstm_chunk_op

    qkv = _spec(one_chip, (1, 1024, 8, 64), jnp.float32)
    gate = _spec(one_chip, (1, 1024, 8), jnp.float32)
    compiled = _compile(
        lambda q, k, v, i, f: mlstm_chunk_op(q, k, v, i, f, chunk=64),
        qkv, qkv, qkv, gate, gate,
    )
    assert _has_kernel(compiled)


def test_seq2seq_train_step_compiles_at_config_widths(one_chip):
    from repro.configs.p3sapp_summarizer import CONFIG
    from repro.models.seq2seq import Seq2Seq
    from repro.optim.adamw import AdamW

    model = Seq2Seq(CONFIG)
    opt = AdamW(learning_rate=1e-3)

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        params, opt_state, _ = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    def on_chip(tree):
        return jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype), tree)

    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = {
        "encoder_tokens": _spec(one_chip, (32, CONFIG.max_abstract_len), jnp.int32),
        "decoder_tokens": _spec(one_chip, (32, CONFIG.max_title_len), jnp.int32),
    }
    _compile(step, on_chip(params), on_chip(jax.eval_shape(opt.init, params)), batch)


def _whole_held_experts(text: str) -> list[str]:
    """The instructions whose value is one layer's 8 held experts whole:
    a (8, 2048, 1408) or (8, 1408, 2048) slice of DeepSeek-V2-Lite's
    routed experts, as a copy, fusion or slice would make it."""
    shape = re.compile(r"= bf16\[(1,)?8,(2048,1408|1408,2048)\]")
    return [line.strip() for line in text.splitlines() if shape.search(line)]


@pytest.mark.parametrize("tokens, path", [(1, "reached"), (64, "ragged")])
def test_dsv2lite_decode_reads_only_the_reached_experts(one_chip, tokens, path):
    """The ep8 share's serve step (one token) compiles with no op that
    copies or reads a layer's 8 held experts whole: the reached path
    indexes each expert in the scan's whole stack and the slice fuses into
    its matrix-vector products. A 64-token prefill keeps the ragged
    grouped GEMM, which takes the layer's slice whole."""
    from repro.configs import get
    from repro.models import moe as MOE
    from repro.models.lm import LM
    from repro.runtime.serve_loop import make_serve_step

    model = LM(get("deepseek_v2_lite_ep8"), remat=False, dtype=jnp.bfloat16)

    def on_chip(tree):
        return jax.tree.map(lambda x: _spec(one_chip, x.shape, x.dtype), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    state = on_chip(jax.eval_shape(lambda: model.init_decode_state(1, 256, jnp.bfloat16)))
    fn = make_serve_step(model) if tokens == 1 else model.decode_step
    with MOE.tally_paths() as took:
        compiled = _compile(fn, params, _spec(one_chip, (1, tokens), jnp.int32), state,
                            _spec(one_chip, (), jnp.int32))
    assert set(took) == {path}
    text = compiled.as_text()
    whole = _whole_held_experts(text)
    if path == "reached":
        assert text.startswith("HloModule jit_serve_step")
        assert not whole, whole[:3]
        assert "ragged" not in text
    else:
        assert whole and "ragged" in text
