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
        return ops._sliding_kernel("conv1d", dzp, wt, 1, interpret=False)

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


# ---------------------------------------------------------------------------
# pinned conv programs
# ---------------------------------------------------------------------------

CELL_B = 16  # whisper-medium.transcribe-30s: 16 requests, 30 s of mels each


def _vjp(f):
    """The custom VJP's forward and backward kernels as one program."""
    def run(*args):
        *primals, dy = args
        return jax.vjp(f, *primals)[1](dy)
    return run


def _frontend(name, cin, stride, precision, chained=False):
    """``ops.conv1d`` as ``whisper.conv_frontend`` calls it through
    ``conv1d_bias_act`` at the cell's batch. ``chained``: the w8a8 chain
    of the calibrated spec — conv1 requantizes onto conv2's grid
    (``out_scale``), conv2 takes conv1's int8 codes."""
    L = FRAMES
    kw = dict(stride=stride, padding="SAME", activation="gelu_erf",
              interpret=False)
    x = ((CELL_B, L, cin), I8 if chained and name == "conv2" else BF16)
    if precision == "fp":
        return (lambda x, w, b: ops.conv1d(x, w, bias=b, **kw),
                [x, ((3, cin, D_MODEL), BF16), ((D_MODEL,), BF16)])
    if chained and name == "conv1":
        return (lambda x, w, s, xs, os, b: ops.conv1d(
                    x, w, bias=b, precision="w8a8", w_scale=s, x_scale=xs,
                    out_scale=os, **kw),
                [x, ((3, cin, D_MODEL), I8), ((D_MODEL,), F32), ((), F32),
                 ((), F32), ((D_MODEL,), BF16)])
    return (lambda x, w, s, xs, b: ops.conv1d(
                x, w, bias=b, precision="w8a8", w_scale=s, x_scale=xs, **kw),
            [x, ((3, cin, D_MODEL), I8), ((D_MODEL,), F32), ((), F32),
             ((D_MODEL,), BF16)])


def _frontend_vjp(name, cin, stride):
    f = lambda x, w, b: ops.conv1d(  # noqa: E731
        x, w, bias=b, stride=stride, padding="SAME", activation="gelu_erf",
        interpret=False)
    return _vjp(f), [((CELL_B, FRAMES, cin), BF16), ((3, cin, D_MODEL), BF16),
                     ((D_MODEL,), BF16),
                     ((CELL_B, FRAMES // stride, D_MODEL), BF16)]


DW = ((1, 2048, 16384), BF16), ((4, 16384), BF16), ((16384,), BF16)
C2 = dict(x=((1, 64, 64, 128), BF16), w=((3, 3, 128, 256), BF16),
          b=((256,), BF16))
C2_KW = dict(padding="SAME", activation="relu", interpret=False)


def _chip_case(name):
    conv = {n: (n, cin, s) for n, cin, s in CONVS}
    if name.startswith("conv1d."):
        _, prec, layer = name.split(".")
        if prec == "vjp":
            return _frontend_vjp(*conv[layer])
        return _frontend(*conv[layer], prec.removesuffix("-chain"),
                         chained=prec.endswith("-chain"))
    if name == "conv1d_depthwise.fp":
        return (lambda x, w, b: ops.conv1d_depthwise(
                    x, w, bias=b, activation="silu", interpret=False),
                list(DW))
    if name == "conv1d_depthwise.w8a8":
        return (lambda x, w, s, xs, b: ops.conv1d_depthwise(
                    x, w, bias=b, activation="silu", precision="w8a8",
                    w_scale=s, x_scale=xs, interpret=False),
                [DW[0], ((4, 16384), I8), ((1, 16384), F32), ((), F32),
                 DW[2]])
    if name == "conv1d_depthwise.vjp":
        return (_vjp(lambda x, w, b: ops.conv1d_depthwise(
                    x, w, bias=b, activation="silu", interpret=False)),
                [*DW, DW[0]])
    if name == "conv2d.fp":
        return (lambda x, w, b: ops.conv2d(x, w, bias=b, **C2_KW),
                [C2["x"], C2["w"], C2["b"]])
    if name == "conv2d.w8a8":
        return (lambda x, w, s, xs, b: ops.conv2d(
                    x, w, bias=b, precision="w8a8", w_scale=s, x_scale=xs,
                    **C2_KW),
                [C2["x"], ((3, 3, 128, 256), I8), ((256,), F32), ((), F32),
                 C2["b"]])
    assert name == "conv2d.vjp", name
    return (_vjp(lambda x, w, b: ops.conv2d(x, w, bias=b, **C2_KW)),
            [C2["x"], C2["w"], C2["b"], ((1, 64, 64, 256), BF16)])


def _model_case(name):
    """The models' conv wrappers at smoke size on the CPU: whisper's
    frontend, mamba's depthwise conv and llava's patch embedding, float
    weights (``fp``), int8 leaves with w8a8 (``w8a8``, whisper's and
    llava's chained as the calibrated spec chains them) and int8 leaves
    served weight-only (``w8a16``)."""
    import numpy as np

    from repro.configs import get_config, smoke_config
    from repro.models import llava, mamba, whisper
    from repro.quant import qconv
    from repro.quant.apply import quantize_depthwise_weight

    model, backend, prec = name.split(".")
    rng = np.random.default_rng(0)
    arr = lambda *s: jnp.asarray(rng.normal(size=s), F32)  # noqa: E731
    xs, os = jnp.float32(0.05), jnp.float32(0.02)
    mode = "w8a8" if prec == "w8a8" else "fp"
    if model == "whisper":
        cfg = smoke_config(get_config("whisper-medium")).replace(
            conv_backend=backend, conv_precision=mode)
        d = cfg.d_model
        p = {"conv1_w": arr(3, whisper.N_MELS, d), "conv1_b": arr(d),
             "conv2_w": arr(3, d, d), "conv2_b": arr(d)}
        if prec != "fp":
            chain = os if prec == "w8a8" else None
            p["conv1_w"] = qconv.quantize_weight(p["conv1_w"], xs, chain)
            p["conv2_w"] = qconv.quantize_weight(p["conv2_w"], os)
        return (lambda p, mels: whisper.conv_frontend(p, mels, cfg),
                (p, arr(2, 64, whisper.N_MELS)))
    if model == "mamba":
        cfg = smoke_config(get_config("jamba-1.5-large-398b")).replace(
            conv_backend=backend, conv_precision=mode)
        C = cfg.mamba_d_inner
        w = arr(cfg.mamba_conv_k, C)
        if prec != "fp":
            w = quantize_depthwise_weight(w, xs)
        return (lambda x, w, b: mamba._conv_act(x, w, b, cfg),
                (arr(2, 64, C), w, arr(C)))
    assert model == "llava", name
    w = arr(llava.PATCH, llava.PATCH, 3, llava.VISION_DIM)
    if prec != "fp":
        w = qconv.quantize_weight(w, xs, os if prec == "w8a8" else None)
    return (lambda w, img, b: llava.patch_embed(
                w, img, backend=backend, bias=b, precision=mode),
            (w, arr(1, 28, 28, 3), arr(llava.VISION_DIM)))


def _without_kernel_locations(text):
    """The lowered text with each Mosaic kernel body printed as MLIR
    without its debug locations. The serialized body carries the Python
    traceback of every op, file paths and the callers' lines included, so
    it moves with the checkout's path and with any edit above the kernel;
    the kernel's operations, types and attributes stay pinned."""
    import base64
    import re

    from jax.experimental.mosaic.dialects import tpu
    from jax.interpreters import mlir
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True  # the body's serialized ops

    def body(m):
        with ctx:
            module = ir.Module.parse(base64.b64decode(m.group(1)))
        return module.operation.get_asm(enable_debug_info=False)

    return re.sub(r"\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22", body, text)


CONV_LOWERED = {
    "conv1d.fp.conv1":
        "db6ca32b98193732bb52e65a6b7af9e3570c28fe4bd49758118047aee304dfca",
    "conv1d.fp.conv2":
        "65d3322c1ead0fe6aab2e0b5a8075d4ec93ed65ee8e2068cae69ef9c4a8b081b",
    "conv1d.w8a8.conv1":
        "9ef6250c74af6f82291529dcbb26505ebe028943d0594e6906bb5fd0ad24250f",
    "conv1d.w8a8.conv2":
        "359d38c85ede01ffa363b661fa24a23157f1c11af170e78aef0e898355245885",
    "conv1d.w8a8-chain.conv1":
        "cee74f9e9586ecfeb86656e56ec086a72a582a7090145f77187d5a6a32e7135f",
    "conv1d.w8a8-chain.conv2":
        "74b1d960c812c2b2c47a69c2a304c231b816c798402352c063d58908fd88623d",
    "conv1d.vjp.conv1":
        "1bc7560baa994e20a66902e0f711b8e65bec4334dfbe1d2cddaf70573ece35a2",
    "conv1d.vjp.conv2":
        "6c4bb9011d7343ffd7bbd3f43452179d501b03c8401d6886c883fa3842eb5e59",
    "conv1d_depthwise.fp":
        "803e56bf54afc2fb3b4d23d075d4e67d7c5b7128038a56ff7d3d07ea195a3289",
    "conv1d_depthwise.w8a8":
        "8819a3872570ae7762972c5290d0ece0486ed62e9a7834e09d1432e7adb215fd",
    "conv1d_depthwise.vjp":
        "f1309e0af140152d7726727486c34f023e0ab9bba2d4a8fb1cfbe7851777ba07",
    "conv2d.fp":
        "00ddb07a1b9dee189e3d7b91237222ae53aed94dc6227bdcf36d19d74df1541f",
    "conv2d.w8a8":
        "bfc594d81d42f73d3458e6ef06f075e9a6744457057af8a2720bca1054921812",
    "conv2d.vjp":
        "411f8b98ea64565a00ef4872e08f368f410358d2867a71a523f5a8eb9267726b",
    "whisper.sliding.fp":
        "1464f2b751d5c5cddaf95f080168577ead5b60bab5a414b2ff6d485a45056ac0",
    "whisper.sliding.w8a8":
        "eff1b78be934316c50718601ac6e55463fcddc7dc9ff1babb9d17ec559e1e63c",
    "whisper.sliding.w8a16":
        "4bfb3a9ae38eeed9561b2e48d9ce4427886374374c3a4a860010d28bb93f604e",
    "whisper.sliding_pallas.fp":
        "bedbd877e8d39fad1df75957e40f374648536c493aa2ee70acf6edb777c84b19",
    "whisper.sliding_pallas.w8a8":
        "638b0aaaa8a36d753007688a1341c9bf09d4f0481d7607bc435df47d1b893fa7",
    "whisper.sliding_pallas.w8a16":
        "41f1b4c9b9a5b5bb338d4e4894b68b2c1da3cdd9e69fa924c4f051f84306a058",
    "mamba.sliding.fp":
        "4110987c6a812fcb99134cdd365b33614ff89f3114e79db9b976966bfdcaff96",
    "mamba.sliding.w8a8":
        "e4d530f684c9dba2fe2a0f6475c45a0aec97fde4d62b8438ef93aed8537091d1",
    "mamba.sliding.w8a16":
        "832cf50447ebc0751f42ca5af8d0d2252151854a340edee2f5f79484025e78c5",
    "mamba.sliding_pallas.fp":
        "293643a8f8968f565b47b27fbdd0ed2cb8887a02badba2c459e61004ddbb6703",
    "mamba.sliding_pallas.w8a8":
        "e4011097ccf620c4fce8a70098061d3529b2a0aac193549d31cfd323be22ad8c",
    "mamba.sliding_pallas.w8a16":
        "6f86c9196d547b855f583d0a0598997610925aa77ee2eb1408062d9297b534bf",
    "mamba.xla.fp":
        "066aababee8a6ee76b957b1d0c37dfc60e2d5bc4e185d10fe1ca36db411dd1de",
    "mamba.xla.w8a8":
        "e4d530f684c9dba2fe2a0f6475c45a0aec97fde4d62b8438ef93aed8537091d1",
    "mamba.xla.w8a16":
        "9879617edb75e4b1d4ad947fe35862696eec0c00fba91d0d7ab1dbc9cc63d724",
    "llava.sliding.fp":
        "723cb6df9554d447b682ee68604237847002709542bf0d4e22ae8c86abb04874",
    "llava.sliding.w8a8":
        "88d21d75d7b590d91c019f3ce1c0b9b4d926a3dc3d0c1e8175c76d757e70ea97",
    "llava.sliding.w8a16":
        "8df1e804053c1054af6b04fed9cac62ba14e9973e25cddd2dc8563e6f4aa44d9",
    "llava.sliding_pallas.fp":
        "f5193a2c38b34f08f1061068586e4501ab953dcf521b4364396134762f853748",
    "llava.sliding_pallas.w8a8":
        "2de80b7f793f663a4abab6d177c1b09961ec3a44a2e4fa6e396b4f7a944f0049",
    "llava.sliding_pallas.w8a16":
        "e9a1436c29e5960e318635903d49ac1569eb0655da9b1f8856181539d09d2319",
}


@pytest.mark.parametrize("case", sorted(CONV_LOWERED))
def test_conv_program_lowers_as_pinned(case, request):
    """Each conv program lowers to the text pinned here by its sha256: the
    kernels as ``ops`` dispatches them for a described v5e
    (``interpret=False``) at whisper-medium's frontend and the chip-smoke
    shapes, forward and custom-VJP backward, fp and w8a8; and the models'
    conv wrappers at smoke size on the CPU under each backend. A change to
    how the kernel is chosen must leave every program as it is. The hashes
    move with the JAX version."""
    import hashlib

    from repro.health import HEALTH

    HEALTH.reset()
    if case.split(".")[0] in ("whisper", "mamba", "llava"):
        fn, args = _model_case(case)
    else:
        sharding = request.getfixturevalue("one_chip")
        fn, shapes = _chip_case(case)
        args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
                for s, d in shapes]
    text = _without_kernel_locations(jax.jit(fn).lower(*args).as_text())
    assert hashlib.sha256(text.encode()).hexdigest() == CONV_LOWERED[case]
