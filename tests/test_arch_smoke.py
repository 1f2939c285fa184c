"""Per-architecture smoke tests: reduced same-family config, one forward +
one train step on CPU; asserts output shapes and finiteness (no NaNs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, smoke_config
from repro.distributed.sharding import ParamDef
from repro.launch.steps import make_prefill_step, make_train_step
from repro.models import build_model
from repro.optim import OptConfig, init_opt_state

B, L = 2, 64


def make_batch(cfg, rng):
    batch = {
        "tokens": jnp.asarray(rng.integers(2, cfg.vocab_size, (B, L)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, L)), jnp.int32),
    }
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(B, L, cfg.d_model)).astype(np.float32)
        )
    if cfg.family == "vlm":
        batch["patches"] = jnp.asarray(
            rng.normal(size=(B, cfg.num_patches, 1152)).astype(np.float32)
        )
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_train_step(arch, rng):
    cfg = smoke_config(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = make_batch(cfg, rng)
    loss = jax.jit(model.loss)(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), f"{arch} loss not finite"
    # near ln(V) at init (uniform predictions)
    assert 2.0 < float(loss) < 2.0 * np.log(cfg.vocab_size)

    opt_cfg = OptConfig(total_steps=10, warmup_steps=2)
    state = {"params": params, "opt": init_opt_state(params, opt_cfg)}
    step = jax.jit(make_train_step(model, opt_cfg))
    new_state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert bool(jnp.isfinite(metrics["grad_norm"]))
    # params actually moved
    moved = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32)))),
        state["params"], new_state["params"],
    )
    assert max(jax.tree.leaves(moved)) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_decode_step(arch, rng):
    cfg = smoke_config(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    cache = jax.tree.map(
        lambda d: jnp.zeros(d.shape, jnp.dtype(d.dtype or cfg.param_dtype)),
        model.cache_defs(B, 32),
        is_leaf=lambda x: isinstance(x, ParamDef),
    )
    tok = jnp.ones((B, 1), jnp.int32)
    logits, written = jax.jit(model.decode_step)(params, cache, tok,
                                                 jnp.int32(0))
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), f"{arch} decode logits not finite"
    # the step returns cache leaves, which merge into a cache of the same
    # structure
    assert set(written) <= set(cache)
    new_cache = {**cache, **written}
    assert jax.tree.structure(cache) == jax.tree.structure(new_cache)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill(arch, rng):
    cfg = smoke_config(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = make_batch(cfg, rng)
    batch.pop("labels")
    logits, cache = jax.jit(make_prefill_step(model))(params, batch)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "rwkv6-1.6b"])
def test_prefill_decode_consistency(arch, rng):
    """Greedy continuation from prefill == decode over the same prompt."""
    cfg = smoke_config(get_config(arch))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    P = 16
    prompt = jnp.asarray(rng.integers(2, cfg.vocab_size, (B, P)), jnp.int32)
    lg_pre, _ = jax.jit(model.prefill)(params, {"tokens": prompt})
    # teacher-forced decode over the prompt must reproduce the same last logits
    cache = jax.tree.map(
        lambda d: jnp.zeros(d.shape, jnp.dtype(d.dtype or cfg.param_dtype)),
        model.cache_defs(B, P + 2),
        is_leaf=lambda x: isinstance(x, ParamDef),
    )
    decode = jax.jit(model.decode_step)
    lg = None
    for i in range(P):
        lg, written = decode(params, cache, prompt[:, i : i + 1], jnp.int32(i))
        cache = {**cache, **written}
    np.testing.assert_allclose(
        np.asarray(lg[:, 0], np.float32),
        np.asarray(lg_pre[:, 0], np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_long_context_skip_table():
    from repro.configs import SHAPES, shape_applicable

    runs = {
        a: shape_applicable(get_config(a), SHAPES["long_500k"])[0]
        for a in ARCH_IDS
    }
    assert runs["rwkv6-1.6b"] and runs["jamba-1.5-large-398b"]
    assert not runs["llama3-8b"] and not runs["gemma-2b"]


def test_rwkv_wkv_chunked_matches_scan(rng):
    """The §Perf-optimized chunked WKV is numerically equivalent to the
    faithful sequential recurrence (both train-mode, random decays)."""
    import jax.numpy as jnp
    from repro.models.rwkv6 import wkv_chunked, wkv_scan

    B_, L_, H_, K_ = 2, 128, 4, 16
    r = jnp.asarray(rng.normal(size=(B_, L_, H_, K_)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B_, L_, H_, K_)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B_, L_, H_, K_)).astype(np.float32))
    logw = -jnp.exp(jnp.asarray(rng.normal(size=(B_, L_, H_, K_)).astype(np.float32)))
    u = jnp.asarray(rng.normal(size=(H_, K_)).astype(np.float32))
    S0 = jnp.zeros((B_, H_, K_, K_), jnp.float32)
    o1, s1 = wkv_scan(r, k, v, logw, u, S0)
    for chunk in (16, 32, 64, 128):
        o2, s2 = wkv_chunked(r, k, v, logw, u, S0, chunk=chunk)
        np.testing.assert_allclose(o1, o2, rtol=3e-3, atol=3e-3)
        np.testing.assert_allclose(s1, s2, rtol=3e-3, atol=3e-3)
