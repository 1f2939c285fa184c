"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Each test lowers one kernel with ``interpret=False`` for a described (not
attached) v5e chip and compiles it with the TPU compiler, at the shapes
``chip_smoke.py`` runs on the chip: whisper-medium's frontend convs (fp,
w8a8, and the backward dx/dw kernels), jamba's depthwise k=4 conv over
16384 channels, ``pool1d``, and decode attention at the qwen3-1.7b and
whisper-medium cache shapes. Nothing runs, so this says nothing about
results or times; it catches what interpret mode cannot — block tilings
the chip refuses, unsupported lowerings, VMEM overruns — at no chip time.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops, sliding_conv_bwd, sliding_pool

BF16, F32, I8 = jnp.bfloat16, jnp.float32, jnp.int8

# (name, Cin, stride) of whisper-medium's frontend over a 30 s clip
CONVS = [("conv1", 80, 1), ("conv2", 1024, 2)]
FRAMES, D_MODEL = 3000, 1024
ATTN = {
    "qwen3-1.7b": dict(B=8, S=4096, KV=8, G=2, D=128),
    "whisper-medium": dict(B=4, S=1500, KV=16, G=1, D=64),
    # the benchmark cell's cross read: 16 slots, 1500 rows allocated at
    # the kernel's block multiple (attention_decode.block_rows)
    "whisper-medium.cross": dict(B=16, S=1536, KV=16, G=1, D=64),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compile is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name,cin,stride", CONVS)
def test_conv1d_fp(one_chip, name, cin, stride):
    _compile(
        one_chip,
        lambda x, w, b: ops.conv1d(
            x, w, bias=b, stride=stride, padding="SAME", activation="gelu_erf",
            interpret=False,
        ),
        ((1, FRAMES, cin), BF16), ((3, cin, D_MODEL), BF16),
        ((D_MODEL,), BF16),
    )


@pytest.mark.parametrize("name,cin,stride", CONVS)
def test_conv1d_w8a8(one_chip, name, cin, stride):
    _compile(
        one_chip,
        lambda x, w, s, xs, b: ops.conv1d(
            x, w, bias=b, stride=stride, padding="SAME", activation="gelu_erf",
            precision="w8a8", w_scale=s, x_scale=xs, interpret=False,
        ),
        ((1, FRAMES, cin), BF16), ((3, cin, D_MODEL), I8),
        ((D_MODEL,), F32), ((), F32), ((D_MODEL,), BF16),
    )


@pytest.mark.parametrize("name,cin,stride", CONVS)
def test_conv1d_bwd_dx(one_chip, name, cin, stride):
    """dx: the forward kernel over the dilated gradient, as the custom VJP
    dispatches it (its own tuned, channel-blocked config)."""

    def dx(dz, w):
        dzp, wt = sliding_conv_bwd.conv1d_dx_operands(dz, w, stride=stride)
        return ops._conv1d_sliding_dispatch(
            dzp, wt, None, activation="none", interpret=False, stride=1,
            tile_l=None, cin_block=None, cout_block=None, regime=None,
        )

    _compile(one_chip, dx, ((1, FRAMES // stride, D_MODEL), BF16),
             ((3, cin, D_MODEL), BF16))


@pytest.mark.parametrize("name,cin,stride", CONVS)
def test_conv1d_bwd_dw(one_chip, name, cin, stride):
    _compile(
        one_chip,
        lambda x, dz: sliding_conv_bwd.conv1d_bwd_dw_pallas(
            x, dz, 3, stride=stride,
            cin_block=ops._auto_block(cin, None),
            cout_block=ops._auto_block(D_MODEL, None),
            has_bias=True, interpret=False,
        ),
        ((1, FRAMES + 2, cin), BF16), ((1, FRAMES // stride, D_MODEL), BF16),
    )


def test_conv1d_depthwise_k4(one_chip):
    _compile(
        one_chip,
        lambda x, w, b: ops.conv1d_depthwise(
            x, w, bias=b, activation="silu", interpret=False
        ),
        ((1, 2048, 16384), BF16), ((4, 16384), BF16), ((16384,), BF16),
    )


@pytest.mark.parametrize("op,window,method", [
    ("sum", 4, "scan"), ("max", 4, "shift"), ("max", 64, "scan"),
])
def test_pool1d(one_chip, op, window, method):
    _compile(
        one_chip,
        lambda x: sliding_pool.sliding_pool_pallas(
            x, window=window, op=op, method=method, interpret=False
        ),
        ((1, 4096, 1024), BF16),
    )


@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("model", sorted(ATTN))
def test_attention_decode(one_chip, model, kind):
    B, S, KV, G, D = (ATTN[model][k] for k in ("B", "S", "KV", "G", "D"))
    cache = ((B, S, KV, D), I8 if kind == "int8" else BF16)
    scales = [((B, S, KV, 1), F32)] * 2 if kind == "int8" else []

    def read(q, k, v, lengths, *kv_scales):
        ks, vs = kv_scales or (None, None)
        return ops.attention_decode(
            q, k, v, lengths=lengths, k_scale=ks, v_scale=vs,
            impl="pallas", interpret=False,
        )

    _compile(one_chip, read, ((B, KV * G, D), BF16), cache, cache,
             ((B,), jnp.int32), *scales)


def test_whisper_decode_step_reads_the_cross_cache_as_stored(
        one_chip, monkeypatch):
    """whisper-medium's fused decode step at the benchmark cell's shapes
    (16 slots, 30 s of audio, 4 + 128 self rows, int8 cache) outputs no
    cross leaf, and hands each layer's cross K/V slice to the Pallas read
    as it is cut from the stacked cache: the cross leaves are stored
    lane-dense (layers, B, rows, KV·hd), so no copy relays a slice out for
    the kernel, as one did on every step for (…, KV, hd) leaves, which
    the TPU stores rows-minor."""
    import re

    from repro.configs import get_config
    from repro.distributed.sharding import ParamDef, Runtime
    from repro.launch import serve
    from repro.models import build_model

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ops, "use_interpret", lambda: False)
    cfg = get_config("whisper-medium").replace(
        kv_quant="int8", attn_decode="fused")
    model = build_model(cfg, Runtime())
    B, P, G = 16, 4, 128
    abstract = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    params = jax.tree.map(lambda a: abstract(a.shape, a.dtype),
                          model.abstract())
    cache = jax.tree.map(
        lambda d: abstract(d.shape, jnp.dtype(d.dtype or cfg.param_dtype)),
        model.cache_defs(B, P + G, enc_len=1500),
        is_leaf=lambda x: isinstance(x, ParamDef))
    rows, lanes = 1536, cfg.num_kv_heads * cfg.resolved_head_dim
    assert cache["xk"].shape == cache["xv"].shape == (
        cfg.num_layers, B, rows, lanes)
    lowered = serve._fused_decode(model).lower(
        params, cache, abstract((B, 1), jnp.int32), abstract((), jnp.int32),
        abstract((B,), jnp.bool_), None, None, None, nan_guard=True)
    assert set(lowered.out_info[3]) == {"k", "v", "k_scale", "v_scale"}
    text = lowered.compile().as_text()
    assert "cross_attention_decode_pallas" in text
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    relaid = re.compile(
        rf"%(copy|transpose)\S* = s8\[{B},{rows},({lanes}|{kv},{hd})\]")
    assert [ln for ln in text.splitlines() if relaid.search(ln)] == []
