"""Hypothesis property tests on the sliding-window invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # container may not ship hypothesis
from hypothesis import given, settings, strategies as st

from repro import core

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


def f32s(lo, hi):
    """Floats in [lo, hi] at float32 width. The strategy needs bounds that
    float32 holds exactly, so each bound snaps inward to the nearest one."""
    lo32, hi32 = np.float32(lo), np.float32(hi)
    if lo32 < lo:
        lo32 = np.nextafter(lo32, np.float32(np.inf))
    if hi32 > hi:
        hi32 = np.nextafter(hi32, np.float32(-np.inf))
    return st.floats(float(lo32), float(hi32), width=32)


def arr(draw, shape, lo=-4, hi=4):
    vals = draw(
        st.lists(
            f32s(lo, hi),
            min_size=int(np.prod(shape)),
            max_size=int(np.prod(shape)),
        )
    )
    return jnp.asarray(np.array(vals, np.float32).reshape(shape))


@given(st.data())
def test_sliding_sum_equals_direct(data):
    n = data.draw(st.integers(4, 40), label="n")
    w = data.draw(st.integers(1, 8), label="w")
    if w > n:
        w = n
    x = arr(data.draw, (2, n))
    got = core.sliding_sum_scan(x, w)
    want = jnp.stack([x[:, i : i + w].sum(-1) for i in range(n - w + 1)], -1)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
    got2 = core.sliding_sum_shift(x, w)
    np.testing.assert_allclose(got2, want, rtol=1e-3, atol=1e-3)


@given(st.data())
def test_conv_linearity(data):
    """conv(a·x + b·y) == a·conv(x) + b·conv(y) — convolution is linear."""
    k = data.draw(st.integers(1, 6), label="k")
    x = arr(data.draw, (1, 16, 2))
    y = arr(data.draw, (1, 16, 2))
    w = arr(data.draw, (k, 2, 3), lo=-2, hi=2)
    a = data.draw(f32s(-2, 2))
    lhs = core.conv1d_sliding(a * x + y, w)
    rhs = a * core.conv1d_sliding(x, w) + core.conv1d_sliding(y, w)
    np.testing.assert_allclose(lhs, rhs, rtol=1e-2, atol=1e-2)


@given(st.data())
def test_conv_shift_equivariance(data):
    """Shifting the input shifts the VALID conv output (translation equiv.)."""
    k = data.draw(st.integers(1, 4), label="k")
    s = data.draw(st.integers(1, 4), label="shift")
    x = arr(data.draw, (1, 24, 2))
    w = arr(data.draw, (k, 2, 2), lo=-2, hi=2)
    full = core.conv1d_sliding(x, w)  # (1, 24-k+1, 2)
    shifted_in = core.conv1d_sliding(x[:, s:], w)
    np.testing.assert_allclose(full[:, s:], shifted_in, rtol=1e-3, atol=1e-3)


@given(st.data())
def test_sliding_backends_agree(data):
    """The paper's claim: all three evaluations compute the same function."""
    k = data.draw(st.integers(1, 8), label="k")
    n = data.draw(st.integers(8, 32), label="n")
    if k > n:
        k = n
    x = arr(data.draw, (1, n, 3))
    w = arr(data.draw, (k, 3, 2), lo=-2, hi=2)
    a = core.conv1d_sliding(x, w)
    b = core.conv1d_im2col(x, w)
    c = core.conv1d_xla(x, w)
    np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(a, c, rtol=1e-3, atol=1e-3)


@given(st.data())
def test_sliding_max_idempotent_monotone(data):
    """max-pool invariants: idempotence on constant rows; monotonicity."""
    n = data.draw(st.integers(6, 30), label="n")
    w = data.draw(st.integers(2, 6), label="w")
    if w > n:
        w = n
    x = arr(data.draw, (1, n))
    y = x + jnp.abs(arr(data.draw, (1, n)))  # y >= x
    mx = core.sliding_max(x, w)
    my = core.sliding_max(y, w)
    assert bool((my >= mx - 1e-6).all())
    const = jnp.full((1, n), 3.25)
    np.testing.assert_allclose(
        core.sliding_max(const, w), jnp.full((1, n - w + 1), 3.25)
    )


@given(st.data())
def test_quantize_roundtrip_error_bound(data):
    """int8 quantization error is bounded by scale/2 per element."""
    from repro.optim import dequantize_int8, quantize_int8

    x = arr(data.draw, (4, 16), lo=-10, hi=10)
    q, s = quantize_int8(x)
    err = jnp.abs(dequantize_int8(q, s) - x)
    assert bool((err <= s * 0.5 + 1e-6).all())


@given(st.data())
def test_quantize_roundtrip_ndim_sweep(data):
    """The optim/compress int8 primitive (shared contract with the quant
    subsystem): round-trip error ≤ scale/2 per element at ndim 0, 1, 2;
    values stay on the int8 grid; dequantized shape matches."""
    from repro.optim import dequantize_int8, quantize_int8

    ndim = data.draw(st.integers(0, 2), label="ndim")
    dims = tuple(
        data.draw(st.integers(1, 12), label=f"d{i}") for i in range(ndim)
    )
    x = (
        jnp.asarray(data.draw(f32s(-50, 50)), jnp.float32)
        if ndim == 0
        else arr(data.draw, dims, lo=-50, hi=50)
    )
    q, s = quantize_int8(x)
    assert q.dtype == jnp.int8
    assert bool((jnp.abs(q.astype(jnp.int32)) <= 127).all())
    back = dequantize_int8(q, s)
    assert back.shape == x.shape
    err = jnp.abs(back - x)
    assert bool((err <= s * 0.5 + 1e-6).all())


@given(st.data())
def test_quantize_zero_rows_exact(data):
    """All-zero rows quantize to exactly zero (the tiny-epsilon scale must
    not manufacture nonzero values), and mixed rows keep per-row scales
    independent — a huge row can't destroy a small row's resolution."""
    from repro.optim import dequantize_int8, quantize_int8

    n = data.draw(st.integers(1, 16), label="n")
    big = data.draw(f32s(100, 1e4), label="big")
    x = np.zeros((3, n), np.float32)
    x[1, :] = big  # rows: zero, big, zero
    q, s = quantize_int8(jnp.asarray(x))
    back = np.asarray(dequantize_int8(q, s))
    np.testing.assert_array_equal(back[0], np.zeros(n, np.float32))
    np.testing.assert_array_equal(back[2], np.zeros(n, np.float32))
    assert np.all(np.abs(back[1] - big) <= float(s[1, 0]) * 0.5 + 1e-3)
    # zero input quantizes to zero codes, not garbage
    assert np.all(np.asarray(q)[0] == 0) and np.all(np.asarray(q)[2] == 0)


@given(st.data())
def test_restart_policy_budget_and_cap(data):
    """RestartPolicy grants exactly ``max_restarts`` backoffs, doubling
    from ``base`` but never past ``cap``, non-decreasing, then None
    forever; ``reset`` restores the full budget."""
    from repro.distributed.ft import RestartPolicy

    max_restarts = data.draw(st.integers(0, 8), label="max_restarts")
    base = data.draw(f32s(0.01, 10), label="base")
    cap = data.draw(f32s(0.01, 100), label="cap")
    p = RestartPolicy(max_restarts=max_restarts, base_backoff_s=base,
                      max_backoff_s=cap)
    delays = [p.next_backoff() for _ in range(max_restarts + 3)]
    granted = delays[:max_restarts]
    assert all(d is not None for d in granted)
    assert all(d is None for d in delays[max_restarts:])  # budget exhausted
    assert all(d <= cap + 1e-9 for d in granted)
    for a, b in zip(granted, granted[1:]):
        assert b >= a - 1e-9  # backoff never shrinks
    if max_restarts:
        assert granted[0] == pytest.approx(min(base, cap))
    p.reset()
    assert (p.next_backoff() is None) == (max_restarts == 0)


@given(st.data())
def test_restart_policy_jitter_monotone_capped_deterministic(data):
    """Seeded jitter preserves the backoff invariants: for any
    ``jitter in [0, 1]`` the granted sequence is still non-decreasing
    (doubling dominates the spread), never exceeds the cap, never drops
    below the unjittered schedule, and is a pure function of
    ``(seed, attempt)`` — two policies with the same seed replay the
    exact delay sequence, different seeds may decorrelate."""
    from repro.distributed.ft import RestartPolicy

    max_restarts = data.draw(st.integers(1, 8), label="max_restarts")
    base = data.draw(f32s(0.01, 10), label="base")
    cap = data.draw(f32s(0.01, 100), label="cap")
    jitter = data.draw(f32s(0.0, 1.0), label="jitter")
    seed = data.draw(st.integers(0, 2**31), label="seed")

    def grants():
        p = RestartPolicy(max_restarts=max_restarts, base_backoff_s=base,
                          max_backoff_s=cap, jitter=jitter, seed=seed)
        return [p.next_backoff() for _ in range(max_restarts)]

    bare = RestartPolicy(max_restarts=max_restarts, base_backoff_s=base,
                         max_backoff_s=cap)
    plain = [bare.next_backoff() for _ in range(max_restarts)]
    granted = grants()
    assert granted == grants()  # deterministic replay
    for a, b in zip(granted, granted[1:]):
        assert b >= a - 1e-9  # doubling dominates jitter <= 1
    for g, p0 in zip(granted, plain):
        assert g <= cap + 1e-9
        assert g >= p0 - 1e-9  # jitter only stretches, never shrinks


@given(st.data())
def test_watchdog_never_flags_during_warmup(data):
    """No straggler flags during warmup (or on the very first step, when
    there is no EMA yet) — whatever the step durations."""
    from repro.distributed.ft import StepWatchdog

    warmup = data.draw(st.integers(0, 6), label="warmup")
    wd = StepWatchdog(threshold=1.01, warmup_steps=warmup)
    for i in range(max(warmup, 1)):
        sec = data.draw(f32s(1e-3, 100), label=f"t{i}")
        assert not wd.observe(i, sec)
    assert wd.events == []


@given(st.data())
def test_watchdog_flags_spike_not_steady_state(data):
    """Constant-duration steps never flag; a spike beyond threshold×EMA
    flags exactly once and a normal step right after does not."""
    from repro.distributed.ft import StepWatchdog

    warmup = data.draw(st.integers(0, 6), label="warmup")
    threshold = data.draw(f32s(1.5, 5), label="threshold")
    base = data.draw(f32s(0.01, 1.0), label="base")
    wd = StepWatchdog(threshold=threshold, warmup_steps=warmup, decay=0.9)
    for i in range(warmup + 8):
        assert not wd.observe(i, base)
    assert wd.observe(99, base * threshold * 1.5)
    assert not wd.observe(100, base)
    assert [s for s, _, _ in wd.events] == [99]


@given(st.data())
def test_watchdog_ema_decays_toward_steady_state(data):
    """The EMA forgets an outlier first step geometrically (rate =
    ``decay``): after n constant steps the distance shrinks by decay^n."""
    from repro.distributed.ft import StepWatchdog

    v0 = data.draw(f32s(1.0, 100), label="v0")
    v = data.draw(f32s(0.01, 1.0), label="v")
    decay = data.draw(f32s(0.1, 0.9), label="decay")
    wd = StepWatchdog(decay=decay, warmup_steps=10_000)  # detection off
    wd.observe(0, v0)
    for i in range(1, 40):
        wd.observe(i, v)
    assert abs(wd.ema - v) <= abs(v0 - v) * decay ** 39 + 1e-6


@given(st.data())
def test_data_pipeline_determinism_and_masking(data):
    from repro.data import SyntheticLMData

    seed = data.draw(st.integers(0, 10_000))
    step = data.draw(st.integers(0, 50))
    d = SyntheticLMData(vocab_size=128, seq_len=64, global_batch=4, seed=seed)
    b1 = d.batch_at(step)
    b2 = d.batch_at(step)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    np.testing.assert_array_equal(b1["labels"], b2["labels"])
    # next-token alignment: where label >= 0 it equals the next input token
    toks, labels = b1["tokens"], b1["labels"]
    m = labels[:, :-1] >= 0
    np.testing.assert_array_equal(
        labels[:, :-1][m], toks[:, 1:][m]
    )
