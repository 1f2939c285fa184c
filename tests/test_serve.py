"""Serving driver (`repro.launch.serve`): generate(), slot recycling,
enc-dec cache sizing and per-request audio, and the int8 KV cache.

The int8 KV contract (DESIGN.md §8): cache leaves with a ``kv_seq`` axis
store int8 codes + a per-(position, head) f32 scale over the head_dim row;
prefill output quantizes before padding, decode steps quantize each new
token's rows in place, attention dequantizes at read. The acceptance
property is behavioral: greedy decode must emit the SAME tokens as the
float cache on the smoke config, with ~2×+ fewer cache bytes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.distributed.sharding import Runtime
from repro.launch import serve
from repro.launch.serve import (
    cache_nbytes,
    generate,
    init_cache_concrete,
    pad_cache_to_defs,
    quantize_cache_to_defs,
)
from repro.models import build_model


def _smoke_model(name="qwen3-1.7b", **overrides):
    cfg = smoke_config(get_config(name)).replace(**overrides)
    model = build_model(cfg, Runtime())
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _prompts(cfg, B=2, P=16, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.integers(2, cfg.vocab_size, size=(B, P)), jnp.int32
    )


def _audio(cfg, B=2, T=32, seed=0):
    """Each request's own mels for an audio model, else None."""
    if cfg.family != "audio":
        return None
    rng = np.random.default_rng(seed + 100)
    return jnp.asarray(rng.uniform(-1, 1, (B, T, 80)), jnp.float32)


# -- done-mask slot recycling -------------------------------------------------

def test_generate_done_mask_slot_recycling():
    """A slot whose sequence hits eos is marked done and keeps emitting eos
    into masked positions; an eos id that can never occur marks nothing."""
    cfg, model, params = _smoke_model(eos_id=-1)  # tokens are >= 0
    prompts = _prompts(cfg)
    toks, done = generate(model, params, prompts, gen_len=6, cache_len=24)
    assert toks.shape == (2, 6)
    assert not bool(done.any())

    # now make the first emitted token of slot 0 the eos id: slot 0 is done
    # from step 0 and every later token in that slot is pinned to eos
    eos = int(toks[0, 0])
    cfg2 = cfg.replace(eos_id=eos)
    model2 = build_model(cfg2, Runtime())
    toks2, done2 = generate(model2, params, prompts, gen_len=6, cache_len=24)
    assert bool(done2[0])
    assert bool((toks2[0] == eos).all())


# -- the fused decode step ---------------------------------------------------

def _eager_loop(model, params, prompts, *, gen_len, cache_len, temperature,
                seed):
    """The decode loop as eager ops: the plain jitted decode, then argmax
    (or a categorical draw from a split key), eos pinning and the done
    update dispatched one by one from Python."""
    logits, cache = serve.prefill_cache(model, params, prompts,
                                        cache_len=cache_len, gen_len=gen_len)
    _, decode = serve._jitted(model)
    eos = jnp.int32(model.cfg.eos_id)
    key = jax.random.key(seed)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    done = tok[:, 0] == eos
    out = [tok]
    P = prompts.shape[1]
    for i in range(gen_len - 1):
        logits, cache = decode(params, cache, tok, jnp.int32(P + i))
        if temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(
                sub, logits[:, -1] / temperature
            ).astype(jnp.int32)[:, None]
        else:
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        tok = jnp.where(done[:, None], eos, tok)
        out.append(tok)
        done = done | (tok[:, 0] == eos)
    return jnp.concatenate(out, axis=1), done


@pytest.mark.parametrize("temperature", [0.0, 0.7])
def test_fused_step_matches_the_eager_loop(temperature):
    """The screen, choice and masking inside the compiled decode step give
    the eager loop's tokens and done mask bit for bit, greedy and sampled,
    with a slot finishing mid-request (its tail pinned to eos); every
    decode step is served by the fused program, and a new temperature
    compiles nothing."""
    from repro.obs import REGISTRY

    cfg, _, params = _smoke_model()
    prompts = _prompts(cfg, B=4, P=8)
    kw = dict(gen_len=8, cache_len=16, temperature=temperature, seed=5)
    # an eos that slot 0 emits at its third token
    probe = build_model(cfg.replace(eos_id=-1), Runtime())
    eos = int(_eager_loop(probe, params, prompts, **kw)[0][0, 2])
    model = build_model(cfg.replace(eos_id=eos), Runtime())
    want, want_done = _eager_loop(model, params, prompts, **kw)
    assert bool(want_done[0]) and bool((want[0, 2:] == eos).all())

    fused = REGISTRY.counter("serve.decode.fused_steps")
    before = fused.value(arch=cfg.name)
    toks, done = generate(model, params, prompts, **kw)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(done), np.asarray(want_done))
    assert fused.value(arch=cfg.name) - before == kw["gen_len"] - 1

    step = serve._fused_decode(model)
    programs = step._cache_size()
    generate(model, params, prompts, **{**kw, "temperature": 2 * temperature})
    assert step._cache_size() == programs
    assert fused.value(arch=cfg.name) - before == 2 * (kw["gen_len"] - 1)


@pytest.mark.parametrize("swap", ["patch", "demotion"])
def test_fused_step_follows_the_jitted_decode(swap):
    """The fused step is built around the decode callable in
    ``_JITTED[model]``: replacing that callable makes the next request
    run the replacement, and dropping it (as a runtime demotion or a
    probation re-jit does) makes the next request trace a fresh one."""
    cfg, model, params = _smoke_model()
    prompts = _prompts(cfg, P=8)
    kw = dict(gen_len=5, cache_len=16)
    clean, _ = generate(model, params, prompts, **kw)
    forced = 7 if int(clean[0, 1]) != 7 else 8
    prefill, decode = serve._jitted(model)

    def wrapped(params, cache, tok, pos):
        logits, new = decode(params, cache, tok, pos)
        return logits.at[..., forced].set(1e9), new

    serve._JITTED[model] = (prefill, wrapped)
    toks, _ = generate(model, params, prompts, **kw)
    assert bool((toks[:, 1:] == forced).all())
    if swap == "demotion":
        serve._JITTED.pop(model)
        toks, _ = generate(model, params, prompts, **kw)
        np.testing.assert_array_equal(np.asarray(toks), np.asarray(clean))
        assert serve._FUSED[model][0] is serve._JITTED[model][1]


# -- enc-dec cache sizing and per-request audio ----------------------------

def test_whisper_generate_clamps_encdec_cache():
    """The cross cache takes one row per encoder position of the audio
    (two mel frames each); the decoder's self-attention rows are prompt +
    gen, whatever the audio, with an undersized cache_len clamped up."""
    cfg, model, params = _smoke_model("whisper-medium")
    P, G = 8, 4
    prompts = _prompts(cfg, B=1, P=P)
    assert serve.resolve_cache_len(cfg, 4, P, G) == P + G
    for frames in (32, 64):
        audio = _audio(cfg, 1, frames)
        _, cache = serve.prefill_cache(model, params, prompts, cache_len=4,
                                       gen_len=G, audio=audio)
        assert cache["xk"].shape[2] == cache["xv"].shape[2] == frames // 2
        assert cache["k"].shape[2] == cache["v"].shape[2] == P + G
        assert bool((cache["enc_len"] == frames // 2).all())
        toks, _ = generate(model, params, prompts, gen_len=G, cache_len=4,
                           audio=audio)
        assert toks.shape == (1, G)


def test_whisper_decoder_positions_bound_the_request():
    """The decoder has 448 learned positions: a prompt + gen beyond them is
    refused, not served on clamped positions."""
    cfg = get_config("whisper-medium")
    assert serve.resolve_cache_len(cfg, 0, 4, 444) == 448
    with pytest.raises(ValueError, match="448"):
        serve.resolve_cache_len(cfg, 0, 4, 445)


def test_audio_family_needs_audio_and_others_refuse_it():
    cfg, model, params = _smoke_model("whisper-medium")
    prompts = _prompts(cfg, B=2, P=4)
    with pytest.raises(ValueError, match="audio"):
        generate(model, params, prompts, gen_len=3, cache_len=8)
    with pytest.raises(ValueError, match="audio"):
        generate(model, params, prompts, gen_len=3, cache_len=8,
                 audio=_audio(cfg, B=3))
    qcfg, qmodel, qparams = _smoke_model()
    with pytest.raises(ValueError, match="no audio"):
        generate(qmodel, qparams, _prompts(qcfg), gen_len=3, cache_len=24,
                 audio=_audio(cfg))


def test_whisper_slot_in_a_batch_equals_the_request_alone():
    """Each slot uses its own audio: a request's first-token logits in a
    batch of two (the other slot with other audio and prompt) equal its
    logits served alone, and the two slots' differ. Float32 smoke model,
    so the batch's and the single request's programs agree to float32
    rounding of logits of order 1 (atol 1e-4 leaves a wide margin)."""
    cfg, model, params = _smoke_model("whisper-medium")
    prompts, audio = _prompts(cfg, B=2, P=4), _audio(cfg, B=2)
    both, _ = serve.prefill_cache(model, params, prompts, cache_len=8,
                                  audio=audio)
    alone, _ = serve.prefill_cache(model, params, prompts[:1], cache_len=8,
                                   audio=audio[:1])
    np.testing.assert_allclose(np.asarray(both[0]), np.asarray(alone[0]),
                               rtol=1e-4, atol=1e-4)
    other, _ = serve.prefill_cache(
        model, params, prompts, cache_len=8,
        audio=audio.at[1].set(audio[0]))
    # slot 1's prompt over slot 0's audio moves slot 1's logits
    assert np.abs(np.asarray(other[1]) - np.asarray(both[1])).max() > 1e-3


def test_replay_refuses_an_audio_journal_record(tmp_path):
    """The journal records prompts, not audio: an audio model's in-flight
    record is refused with a clear error, not replayed without audio."""
    cfg, model, params = _smoke_model("whisper-medium")
    journal = serve.RequestJournal(tmp_path)
    journal.begin("req0", _prompts(cfg, B=1, P=4), gen_len=3, cache_len=8,
                  temperature=0.0, seed=0)
    with pytest.raises(ValueError, match="audio"):
        serve.replay_pending(model, params, journal)
    assert journal.pending()  # still in flight, not ended


#: sha256 of qwen3-1.7b's smoke prefill and int8-cache decode programs as
#: lowered by the tree before whisper's biases entered the shared layers
QWEN3_SMOKE_LOWERED = (
    "cea7068eb583c3b4e48413cd906936b45bc1cc6fdfb7e3965a3ba77264b34625",
    "51c2d4735b27306639ce351d97b2b33dafbbc1b9c7d7201f50a24460d078f77f",
)


def test_qwen3_programs_take_no_bias_ops(monkeypatch):
    """qwen3-1.7b's tree holds no bias leaves, so the shared layers' bias
    hooks (``layers.add_bias``, whisper's biases) add no operation: its
    smoke prefill and int8-cache decode programs lower to the same text
    with the hooks taken out, and to the text the layers lowered to
    before the hooks (pinned by hash; it moves with the JAX version)."""
    import hashlib

    from repro.models import layers

    cfg, model, params = _smoke_model(kv_quant="int8")
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    names = {getattr(k, "key", None) for path, _ in leaves for k in path}
    assert not names & {"bq", "bk", "bv", "bo", "bi"}
    prompts = _prompts(cfg)
    cache = init_cache_concrete(model, 2, 24)
    tok = prompts[:, :1]

    def lowered():
        return (jax.jit(model.prefill).lower(params, {"tokens": prompts})
                .as_text(),
                jax.jit(model.decode_step).lower(params, cache, tok,
                                                 jnp.int32(16)).as_text())

    with_hooks = lowered()
    assert tuple(hashlib.sha256(t.encode()).hexdigest()
                 for t in with_hooks) == QWEN3_SMOKE_LOWERED
    monkeypatch.setattr(layers, "add_bias", lambda y, p, name: y)
    assert lowered() == with_hooks


#: sha256 of qwen3-1.7b's smoke int8-cache fused decode step (greedy,
#: nan_guard) as lowered by the tree before decode steps returned only the
#: leaves they wrote
QWEN3_SMOKE_FUSED_LOWERED = (
    "537a88ff7d1d074b2466d8924006418f89aa6cfa4127527429dcfcf84ff6abc1")


def _lower_fused(model, params, cache, P, *, key=None, temperature=None):
    B = cache["k"].shape[1]
    return serve._fused_decode(model).lower(
        params, cache, jnp.ones((B, 1), jnp.int32), jnp.int32(P),
        jnp.zeros((B,), bool), temperature, key, None, nan_guard=True)


@pytest.mark.parametrize("arch", ["whisper-medium", "qwen3-1.7b"])
def test_fused_decode_step_outputs_only_the_leaves_it_writes(arch):
    """The compiled decode step's results are the token, the done and bad
    masks, the key, and the cache leaves the model's step writes: for
    whisper the self-attention K/V and their scales, never a cross leaf
    (``xk``, ``xv``, their scales, ``enc_len``), which it only reads; for
    qwen3 the whole cache, in a program that lowers to the same text as
    before (pinned by hash; it moves with the JAX version)."""
    import hashlib

    cfg, model, params = _smoke_model(arch, kv_quant="int8")
    B, P, G = 2, 8, 4
    prompts = _prompts(cfg, B=B, P=P)
    _, cache = serve.prefill_cache(model, params, prompts, cache_len=24,
                                   gen_len=G, audio=_audio(cfg, B=B, T=64))
    lowered = _lower_fused(model, params, cache, P,
                           key=jax.random.key(0), temperature=jnp.float32(.7))
    tok, done, bad, written, key = lowered.out_info
    assert (tok.shape, done.shape, bad.shape) == ((B, 1), (B,), (B,))
    assert key.shape == ()
    if arch == "qwen3-1.7b":
        assert set(written) == set(cache)
        text = _lower_fused(model, params, cache, P).as_text()
        assert (hashlib.sha256(text.encode()).hexdigest()
                == QWEN3_SMOKE_FUSED_LOWERED)
        return
    assert set(written) == {"k", "v", "k_scale", "v_scale"}
    cross = {(cache[n].shape, cache[n].dtype)
             for n in ("xk", "xv", "xk_scale", "xv_scale", "enc_len")}
    outs = {(o.shape, o.dtype) for o in jax.tree.leaves(lowered.out_info)}
    assert cross.isdisjoint(outs)
    for n, o in written.items():
        assert (o.shape, o.dtype) == (cache[n].shape, cache[n].dtype)


def _cross_pads(text, B, rows):
    """The lowered program's pads of a (B, rows, ...) per-layer cross leaf."""
    return [ln for ln in text.splitlines()
            if "stablehlo.pad" in ln and f": (tensor<{B}x{rows}x" in ln]


def test_whisper_cross_rows_round_to_the_kernel_block(monkeypatch, tmp_path):
    """200 encoder positions take 512 cross rows (the kernel's block
    multiple), padded once at prefill: ``enc_len`` keeps 200, the rows
    past it are zero codes and zero scales, and with a tuned 128-row block
    the compiled decode step pads no cross leaf, where a cache of the
    unrounded 200 rows is padded on every step. The greedy tokens equal
    the unrounded cache's. ``serve.decode.held_cache_bytes`` reads the
    cross leaves' bytes, which no step returns; qwen3's step returns its
    whole cache and reads 0."""
    from repro.kernels import attention_decode as attn_dec
    from repro.kernels import autotune
    from repro.obs import REGISTRY

    cfg, _, params = _smoke_model("whisper-medium", kv_quant="int8")
    B, P, G, rows = 2, 4, 4, 200
    KV, H, D = cfg.num_kv_heads, cfg.num_heads, cfg.resolved_head_dim
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    autotune.invalidate()
    for S in (rows, 512):
        autotune.record(autotune.attn_dec_key(B, S, KV, H // KV, D, "int8"),
                        {"block_s": 128})
    prompts = _prompts(cfg, B=B, P=P)
    audio = _audio(cfg, B=B, T=2 * rows)
    held = REGISTRY.gauge("serve.decode.held_cache_bytes")

    def serve_once():
        model = build_model(cfg, Runtime())
        _, cache = serve.prefill_cache(model, params, prompts,
                                       cache_len=P + G, gen_len=G,
                                       audio=audio)
        toks, _ = generate(model, params, prompts, gen_len=G,
                           cache_len=P + G, audio=audio)
        text = _lower_fused(model, params, cache, P).as_text()
        return cache, np.asarray(toks), text, held.value(arch=cfg.name)

    cache, toks, text, held_bytes = serve_once()
    assert cache["xk"].shape[2] == cache["xv_scale"].shape[2] == 512
    assert bool((cache["enc_len"] == rows).all())
    for n in ("xk", "xv", "xk_scale", "xv_scale"):
        assert bool((cache[n][:, :, rows:] == 0).all()), n
        assert bool((cache[n][:, :, :rows] != 0).any()), n
    assert _cross_pads(text, B, 512) == []
    assert held_bytes == sum(cache[n].nbytes for n in (
        "xk", "xv", "xk_scale", "xv_scale", "enc_len"))

    monkeypatch.setattr(attn_dec, "block_rows", lambda r: r)
    ucache, utoks, utext, _ = serve_once()
    autotune.invalidate()
    assert ucache["xk"].shape[2] == rows
    assert len(_cross_pads(utext, B, rows)) > 0
    np.testing.assert_array_equal(toks, utoks)

    qcfg, qmodel, qparams = _smoke_model(kv_quant="int8")
    generate(qmodel, qparams, _prompts(qcfg), gen_len=3, cache_len=24)
    assert held.value(arch=qcfg.name) == 0.0


@pytest.mark.parametrize("attn_decode", ["fused", "view"])
def test_whisper_cross_cache_is_stored_lane_dense(attn_decode):
    """The served cross K/V are (layers, B, rows, KV·hd), the layout the
    decode kernel reads, with one scale per (row, head): prefill quantizes
    each head's row and then merges the heads. A decode step over them
    gives the same logits, bit for bit, as over the same codes with the
    heads apart, (…, KV, hd) as prefill emits them, in the fused read and
    in the dequantized view alike."""
    cfg, _, params = _smoke_model("whisper-medium", kv_quant="int8",
                                  attn_decode=attn_decode)
    model = build_model(cfg, Runtime())
    B, P, G = 2, 4, 3
    KV, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    _, cache = serve.prefill_cache(model, params, _prompts(cfg, B=B, P=P),
                                   cache_len=P + G, gen_len=G,
                                   audio=_audio(cfg, B=B, T=64))
    rows = (cfg.num_layers, B, 32)
    assert cache["xk"].shape == cache["xv"].shape == (*rows, KV * hd)
    assert cache["xk_scale"].shape == cache["xv_scale"].shape == (*rows, KV, 1)
    apart = {**cache, **{n: cache[n].reshape(*rows, KV, hd)
                         for n in ("xk", "xv")}}
    step = jax.jit(model.decode_step)
    tok = jnp.ones((B, 1), jnp.int32)
    dense, _ = step(params, cache, tok, jnp.int32(P))
    heads, _ = step(params, apart, tok, jnp.int32(P))
    assert bool(jnp.isfinite(dense).all())
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(heads))


# -- int8 KV cache ------------------------------------------------------------

def test_kv_cache_int8_roundtrip_greedy_tokens_match():
    """Greedy decode with the int8 KV cache matches the float-cache tokens
    on the smoke config, and the cache defs report ≥2× fewer bytes."""
    cfg, model, params = _smoke_model()
    prompts = _prompts(cfg)
    toks_fp, _ = generate(model, params, prompts, gen_len=8, cache_len=24)

    qcfg = cfg.replace(kv_quant="int8")
    qmodel = build_model(qcfg, Runtime())
    toks_q, _ = generate(qmodel, params, prompts, gen_len=8, cache_len=24)
    np.testing.assert_array_equal(np.asarray(toks_fp), np.asarray(toks_q))

    b_fp = cache_nbytes(model.cache_defs(2, 24), cfg.param_dtype)
    b_q = cache_nbytes(qmodel.cache_defs(2, 24), qcfg.param_dtype)
    assert b_fp / b_q >= 2.0, (b_fp, b_q)


def test_kv_cache_int8_defs_pair_and_pad_coherently():
    """Every int8 cache leaf has a kv_seq-named ``_scale`` sibling, and
    pad_cache_to_defs pads the (q, scale) pair along the same axis."""
    cfg, model, params = _smoke_model(kv_quant="int8")
    B, P, S = 2, 8, 24
    prompts = _prompts(cfg, P=P)
    logits, cache = jax.jit(model.prefill)(params, {"tokens": prompts})
    defs = model.cache_defs(B, S)
    for name, d in defs.items():
        if d.dtype == "int8":
            sd = defs[f"{name}_scale"]
            assert "kv_seq" in sd.axes and sd.shape[-1] == 1

    qcache = quantize_cache_to_defs(cache, defs)
    assert qcache["k"].dtype == jnp.int8
    assert qcache["k_scale"].dtype == jnp.float32
    # round trip: dequantized codes reproduce the prefill KV to int8 error
    deq = qcache["k"].astype(jnp.float32) * qcache["k_scale"]
    err = jnp.abs(deq - cache["k"].astype(jnp.float32))
    assert float(err.max()) <= float(qcache["k_scale"].max()) * 0.5 + 1e-6

    full = init_cache_concrete(model, B, S)
    padded = pad_cache_to_defs(qcache, full, defs)
    assert padded["k"].shape[2] == S and padded["k_scale"].shape[2] == S
    # padded tail rows: zero codes AND zero scales (dequant to 0, masked)
    assert bool((padded["k"][:, :, P:] == 0).all())
    assert bool((padded["k_scale"][:, :, P:] == 0).all())


@pytest.mark.parametrize("arch", ["jamba-1.5-large-398b", "whisper-medium"])
def test_kv_cache_int8_decode_runs_other_families(arch):
    """Hybrid (jamba: KV + recurrent states) and enc-dec (whisper: xk/xv
    cross leaves) decode end to end with the int8 cache."""
    cfg, model, params = _smoke_model(arch, kv_quant="int8")
    prompts, audio = _prompts(cfg, B=1, P=8), _audio(cfg, B=1)
    toks, _ = generate(model, params, prompts, gen_len=4, cache_len=24,
                       audio=audio)
    assert toks.shape == (1, 4)

    fp = build_model(cfg.replace(kv_quant="fp"), Runtime())
    toks_fp, _ = generate(fp, params, prompts, gen_len=4, cache_len=24,
                          audio=audio)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(toks_fp))


# -- fused decode-attention read (DESIGN.md §9) -------------------------------

@pytest.mark.parametrize(
    "arch", ["qwen3-1.7b", "jamba-1.5-large-398b", "whisper-medium"]
)
@pytest.mark.parametrize("kvq", ["fp", "int8"])
def test_fused_decode_read_matches_view_path(arch, kvq):
    """Greedy tokens from the fused flash read (int8 codes resident, no
    float K/V view) are identical to the PR-4 dequant-at-read path —
    on the fp cache too (the fp variant shares the kernel)."""
    cfg, model, params = _smoke_model(arch, kv_quant=kvq)  # fused default
    assert cfg.attn_decode == "fused"
    prompts, audio = _prompts(cfg), _audio(cfg)
    toks_fused, _ = generate(model, params, prompts, gen_len=6, cache_len=24,
                             audio=audio)

    view = build_model(cfg.replace(attn_decode="view"), Runtime())
    toks_view, _ = generate(view, params, prompts, gen_len=6, cache_len=24,
                            audio=audio)
    np.testing.assert_array_equal(
        np.asarray(toks_fused), np.asarray(toks_view)
    )


def test_fused_decode_dispatch_logged():
    """Serving through the fused read records its autotune shape key —
    the line serve's CLI prints and CI asserts on."""
    from repro.kernels import ops as kops

    cfg, model, params = _smoke_model(kv_quant="int8")
    kops.ATTN_DECODE_DISPATCH.clear()
    generate(model, params, _prompts(cfg), gen_len=3, cache_len=24)
    assert any(
        k.startswith("attn_dec|") and "|int8" in k
        for k in kops.ATTN_DECODE_DISPATCH
    ), kops.ATTN_DECODE_DISPATCH


def test_store_kv_token_pair_updates_together():
    """The shared (q, scale) pair helper writes both leaves at the same
    position on the same grid as the prefill-cache quantizer."""
    import jax.numpy as jnp

    from repro.models.common import quantize_kv_leaf, store_kv_token

    rng = np.random.default_rng(0)
    cache = {
        "k": jnp.zeros((2, 8, 2, 16), jnp.int8),
        "k_scale": jnp.zeros((2, 8, 2, 1), jnp.float32),
    }
    fresh = jnp.asarray(rng.normal(size=(2, 1, 2, 16)).astype(np.float32))
    new = store_kv_token(cache, "k", fresh, jnp.int32(3))
    q, s = quantize_kv_leaf(fresh)
    np.testing.assert_array_equal(np.asarray(new["k"][:, 3:4]), np.asarray(q))
    np.testing.assert_array_equal(
        np.asarray(new["k_scale"][:, 3:4]), np.asarray(s)
    )
    assert bool((np.asarray(new["k"][:, :3]) == 0).all())
    # float cache: no scale sibling, plain write
    fp = {"k": jnp.zeros((2, 8, 2, 16), jnp.float32)}
    out = store_kv_token(fp, "k", fresh, jnp.int32(0))
    assert set(out) == {"k"}
    np.testing.assert_allclose(
        np.asarray(out["k"][:, 0:1]), np.asarray(fresh), rtol=1e-6
    )
