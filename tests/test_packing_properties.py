"""Property tests for the packing helpers (`repro.core.packing`).

These are the algebraic contracts the adaptive-temporal machinery leans on:

- `popcount(pack_spikes(s))` is exactly the per-neuron spike count, so the
  neuron-level activity scorer never needs the unpacked tensor;
- `timestep_popcount(pack_spikes(s), T)` is exactly `s.sum()` per timestep
  plane, so the timestep scorer (`timestep_activity_map`) is a faithful
  device-side reduction of the original (T, ...) tensor;
- both maskers are idempotent and `min_spikes=1` timestep masking is the
  identity — the formal statement of "adaptive(min_spikes=1) is bitwise".

Strategies draw T from the full supported range [1, 32] (MAX_T) plus
density, so the all-silent and all-dense corners are hit both by dedicated
tests and by the random sweep.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from _hyp import given, settings, st
from repro.core.packing import (
    MAX_T,
    encode_event_window,
    mask_low_activity,
    mask_low_activity_timesteps,
    pack_spikes,
    popcount,
    timestep_activity_map,
    timestep_popcount,
    unpack_spikes,
)


def _random_spikes(T: int, n: int, density: float, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return (rng.random((T, n)) < density).astype(np.float32)


@settings(max_examples=30, deadline=None)
@given(
    T=st.integers(min_value=1, max_value=MAX_T),
    density=st.floats(min_value=0.0, max_value=1.0),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_popcount_equals_time_sum(T, density, seed):
    """popcount(pack_spikes(s)) == s.sum(axis=0) for every neuron."""
    s = _random_spikes(T, 64, density, seed)
    packed = pack_spikes(jnp.asarray(s))
    np.testing.assert_array_equal(
        np.asarray(popcount(packed)), s.sum(axis=0).astype(np.int32)
    )


@settings(max_examples=30, deadline=None)
@given(
    T=st.integers(min_value=1, max_value=MAX_T),
    density=st.floats(min_value=0.0, max_value=1.0),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_timestep_popcount_equals_plane_sum(T, density, seed):
    """timestep_popcount(pack_spikes(s), T)[t] == s[t].sum() exactly."""
    s = _random_spikes(T, 64, density, seed)
    packed = pack_spikes(jnp.asarray(s))
    got = np.asarray(timestep_popcount(packed, T))
    assert got.shape == (T,)
    np.testing.assert_array_equal(got, s.sum(axis=1).astype(np.int32))


@settings(max_examples=30, deadline=None)
@given(
    T=st.integers(min_value=1, max_value=MAX_T),
    density=st.floats(min_value=0.0, max_value=1.0),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_pack_unpack_roundtrip(T, density, seed):
    s = _random_spikes(T, 48, density, seed)
    packed = pack_spikes(jnp.asarray(s))
    np.testing.assert_array_equal(np.asarray(unpack_spikes(packed, T)), s)


@settings(max_examples=25, deadline=None)
@given(
    T=st.integers(min_value=1, max_value=MAX_T),
    min_spikes=st.integers(min_value=1, max_value=4),
    density=st.floats(min_value=0.0, max_value=1.0),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_mask_low_activity_idempotent(T, min_spikes, density, seed):
    """Masking an already-masked word changes nothing (neuron axis)."""
    s = _random_spikes(T, 64, density, seed)
    packed = pack_spikes(jnp.asarray(s))
    once = mask_low_activity(packed, min_spikes=min_spikes)
    twice = mask_low_activity(once, min_spikes=min_spikes)
    np.testing.assert_array_equal(np.asarray(once), np.asarray(twice))
    # survivors still meet the threshold; victims are fully zeroed
    pc = np.asarray(popcount(once))
    assert np.all((pc == 0) | (pc >= min_spikes))


@settings(max_examples=25, deadline=None)
@given(
    T=st.integers(min_value=1, max_value=MAX_T),
    min_spikes=st.integers(min_value=1, max_value=4),
    density=st.floats(min_value=0.0, max_value=1.0),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_mask_low_activity_timesteps_idempotent(T, min_spikes, density, seed):
    """Masking an already-masked tensor changes nothing (timestep axis)."""
    s = _random_spikes(T, 64, density, seed)
    packed = pack_spikes(jnp.asarray(s))
    once = mask_low_activity_timesteps(packed, T, min_spikes=min_spikes)
    twice = mask_low_activity_timesteps(once, T, min_spikes=min_spikes)
    np.testing.assert_array_equal(np.asarray(once), np.asarray(twice))
    # surviving planes still meet the threshold; dropped planes are zero
    tpc = np.asarray(timestep_popcount(once, T))
    assert np.all((tpc == 0) | (tpc >= min_spikes))


@settings(max_examples=25, deadline=None)
@given(
    T=st.integers(min_value=1, max_value=MAX_T),
    density=st.floats(min_value=0.0, max_value=1.0),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_mask_timesteps_min_spikes_1_is_identity(T, density, seed):
    """min_spikes=1 keeps every plane with >=1 spike and only zeroes planes
    that are already all-zero — i.e. it is the identity.  This is the
    algebraic core of the bitwise guarantee for adaptive(min_spikes=1)."""
    s = _random_spikes(T, 64, density, seed)
    packed = pack_spikes(jnp.asarray(s))
    masked = mask_low_activity_timesteps(packed, T, min_spikes=1)
    np.testing.assert_array_equal(np.asarray(masked), np.asarray(packed))


@pytest.mark.parametrize("T", [1, 3, 8, 16, MAX_T])
def test_all_silent_edge(T):
    """All-silent input: every plane scored inactive, masking is a no-op on
    the zero word, popcounts are zero."""
    packed = jnp.zeros((32,), jnp.uint32)
    np.testing.assert_array_equal(
        np.asarray(timestep_popcount(packed, T)), np.zeros((T,), np.int32)
    )
    assert not np.asarray(timestep_activity_map(packed, T)).any()
    np.testing.assert_array_equal(
        np.asarray(mask_low_activity_timesteps(packed, T, min_spikes=3)),
        np.zeros((32,), np.uint32),
    )


@pytest.mark.parametrize("T", [1, 3, 8, 16, MAX_T])
def test_all_dense_edge(T):
    """All-dense input: every plane active at any threshold <= n, masking
    preserves the word exactly (including at thresholds > 1)."""
    s = np.ones((T, 16), np.float32)
    packed = pack_spikes(jnp.asarray(s))
    np.testing.assert_array_equal(
        np.asarray(timestep_popcount(packed, T)), np.full((T,), 16, np.int32)
    )
    assert np.asarray(timestep_activity_map(packed, T, min_spikes=16)).all()
    np.testing.assert_array_equal(
        np.asarray(mask_low_activity_timesteps(packed, T, min_spikes=16)),
        np.asarray(packed),
    )


def test_mask_timesteps_preserves_bits_above_T():
    """Bits at positions >= T (not part of the logical trace) are never
    touched by timestep masking — the mask word only covers [0, T)."""
    # word with bit 7 set; logical T=4, plane threshold drops bits 0..3
    packed = jnp.asarray([0b1000_0011], jnp.uint32)
    masked = mask_low_activity_timesteps(packed, T=4, min_spikes=2)
    # popcount per plane in [0,4) is 1 < 2 -> those bits cleared; bit 7 kept
    assert int(np.asarray(masked)[0]) == 0b1000_0000


def test_timestep_popcount_rejects_T_over_max():
    with pytest.raises(ValueError):
        timestep_popcount(jnp.zeros((4,), jnp.uint32), MAX_T + 1)


# ---------------------------------------------------------------------------
# encode_event_window (the event-stream ingestion encoder, serve/streaming.py)
# ---------------------------------------------------------------------------


def _event_plane_oracle(ev, height, width, T, window_us, t0):
    """Reference binning in plain numpy: a pixel fires at plane tau iff any
    in-window, in-extent event lands in its bin."""
    plane = np.zeros((T, height * width), np.float32)
    for x, y, _p, t in ev:
        rel = t - t0
        if 0 <= rel < window_us and 0 <= x < width and 0 <= y < height:
            plane[rel * T // window_us, y * width + x] = 1.0
    return plane


@settings(max_examples=25, deadline=None)
@given(
    T=st.integers(min_value=1, max_value=MAX_T),
    n=st.integers(min_value=0, max_value=96),
    window_us=st.sampled_from([1, 7, 100, 1000]),
    t0_windows=st.integers(min_value=0, max_value=3),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_encode_event_window_roundtrip(T, n, window_us, t0_windows, seed):
    """event -> packed -> unpack_spikes sets EXACTLY the bins of valid
    events: every in-window in-extent event's (tau, pixel) bit is set, no
    spurious bit appears, and out-of-window/out-of-extent rows (drawn past
    the sensor and window on purpose) are ignored — the oracle is a plain
    numpy re-binning."""
    height, width = 5, 6
    t0 = t0_windows * window_us
    rng = np.random.default_rng(seed)
    ev = np.stack(
        [
            rng.integers(-2, width + 2, n),       # x, some out of extent
            rng.integers(-2, height + 2, n),      # y, some out of extent
            rng.integers(0, 2, n),                # polarity (ignored)
            rng.integers(max(0, t0 - window_us), t0 + 2 * window_us, n),
        ],
        axis=1,
    ).astype(np.int64) if n else np.zeros((0, 4), np.int64)
    words = encode_event_window(ev, height, width, T, window_us, t0=t0)
    np.testing.assert_array_equal(
        np.asarray(unpack_spikes(words, T)),
        _event_plane_oracle(ev, height, width, T, window_us, t0),
    )


@settings(max_examples=25, deadline=None)
@given(
    T=st.integers(min_value=1, max_value=MAX_T),
    window_us=st.sampled_from([1, 13, 1000]),
    t0_windows=st.integers(min_value=0, max_value=3),
)
def test_encode_event_window_boundary_exactness(T, window_us, t0_windows):
    """Window edges are exact: t0 lands in plane 0 and t0 + window_us - 1
    in the last occupied plane ``(window_us - 1) * T // window_us`` (== T-1
    whenever T <= window_us), while t0 - 1 and t0 + window_us contribute
    nothing."""
    height = width = 4
    t0 = t0_windows * window_us
    inside = np.asarray(
        [[1, 1, 0, t0], [2, 2, 1, t0 + window_us - 1]], np.int64
    )
    words = np.asarray(encode_event_window(
        inside, height, width, T, window_us, t0=t0))
    s = np.asarray(unpack_spikes(jnp.asarray(words), T))
    last = (window_us - 1) * T // window_us
    if T <= window_us:
        assert last == T - 1
    assert s[0, 1 * width + 1] == 1.0
    assert s[last, 2 * width + 2] == 1.0
    assert s.sum() == 2.0  # distinct pixels: nothing else fired
    outside = np.asarray(
        [[1, 1, 0, t0 - 1], [2, 2, 1, t0 + window_us]], np.int64
    )
    if t0 == 0:
        outside = outside[1:]  # t=-1 is invalid input anyway
    out_words = np.asarray(encode_event_window(
        outside, height, width, T, window_us, t0=t0))
    assert (out_words == 0).all()


@settings(max_examples=10, deadline=None)
@given(T=st.integers(min_value=1, max_value=MAX_T))
def test_encode_event_window_empty_is_all_silent(T):
    """An empty window encodes to the all-silent frame: zero words, zero
    per-plane popcount, every plane scored inactive — the frame the
    adaptive temporal policy skips for free."""
    words = encode_event_window(
        np.zeros((0, 4), np.int64), 4, 4, T, 1000, t0=0
    )
    assert (np.asarray(words) == 0).all()
    np.testing.assert_array_equal(
        np.asarray(timestep_popcount(words, T)), np.zeros((T,), np.int32)
    )
    assert not np.asarray(timestep_activity_map(words, T)).any()


def test_encode_event_window_validation():
    ev = np.zeros((0, 4), np.int64)
    with pytest.raises(ValueError):
        encode_event_window(ev, 4, 4, MAX_T + 1, 100)
    with pytest.raises(ValueError):
        encode_event_window(ev, 4, 4, 0, 100)
    with pytest.raises(ValueError):
        encode_event_window(ev, 0, 4, 4, 100)
    with pytest.raises(ValueError):
        encode_event_window(ev, 4, 4, 4, 0)
