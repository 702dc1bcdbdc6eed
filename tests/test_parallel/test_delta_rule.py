"""The gated delta rule: the chunkwise form with its Pallas kernel
(``gated_delta_chunk``, interpreted on the CPU) and the single-step form,
against the recurrence written out token by token as the equations read.

Everything is float32 on seeded random inputs shaped as the model makes them
(unit keys, queries of norm ``dk ** -0.5``, ``beta`` in (0, 2), decays from
nearly none to strong).  Tolerances: outputs are O(0.1-1) and the state O(1);
the three forms differ only in the order of float32 sums over at most a
sub-chunk of 64 (and, for the state, over the sequence), so 2e-5 absolute on
both has two orders of room above what they read (1e-7 to 5e-7) and is a
thousand times tighter than one bfloat16 rounding of the state (4e-3).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from polyaxon_tpu.parallel import delta_rule

TOL = 2e-5


def _inputs(T, H, dk, dv, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    q = jax.random.normal(ks[0], (T, H, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * dk**-0.5
    k = jax.random.normal(ks[1], (T, H, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (T, H, dv))
    # per-head decay rates over three orders of magnitude, as A and dt give them
    rate = jnp.exp(jax.random.uniform(ks[3], (1, H), minval=-7.0, maxval=0.5))
    g = -rate * jax.random.uniform(ks[4], (T, H), minval=0.2, maxval=2.0)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[5], (T, H)))
    s0 = 0.3 * jax.random.normal(ks[6], (H, dv, dk))
    return q, k, v, g, beta, s0


def _token_by_token(q, k, v, g, beta, s0):
    """S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T; o_t = S_t q_t,
    in float64 numpy, a loop over tokens and heads."""
    q, k, v, g, beta = (np.asarray(x, np.float64) for x in (q, k, v, g, beta))
    S = np.array(s0, np.float64)
    out = np.zeros(v.shape)
    eye = np.eye(q.shape[-1])
    for t in range(q.shape[0]):
        for h in range(q.shape[1]):
            kk = np.outer(k[t, h], k[t, h])
            S[h] = np.exp(g[t, h]) * S[h] @ (eye - beta[t, h] * kk) + beta[t, h] * np.outer(
                v[t, h], k[t, h])
            out[t, h] = S[h] @ q[t, h]
    return out, S


# Head sizes 96 and 192 both ways round; lengths that are no multiple of the
# sub-chunk (100 = 64 + 36, 200 = 3 x 64 + 8), one shorter than a sub-chunk,
# and one that is a whole number of them.
CASES = [(100, 2, 96, 192), (200, 2, 192, 96), (24, 3, 96, 192), (128, 1, 96, 192)]


@pytest.mark.parametrize("T,H,dk,dv", CASES)
def test_chunkwise_form_and_kernel_agree_with_the_recurrence(T, H, dk, dv):
    args = _inputs(T, H, dk, dv, seed=T)
    want_o, want_s = _token_by_token(*args)
    o, s = jax.jit(delta_rule.gated_delta_prefill)(*args)
    assert o.shape == (T, H, dv) and s.shape == (H, dv, dk)
    assert float(np.max(np.abs(np.asarray(o) - want_o))) < TOL
    assert float(np.max(np.abs(np.asarray(s) - want_s))) < TOL


@pytest.mark.parametrize("T,H,dk,dv", CASES[:2])
def test_single_step_form_agrees_with_the_recurrence(T, H, dk, dv):
    q, k, v, g, beta, s0 = _inputs(T, H, dk, dv, seed=T + 1)
    want_o, want_s = _token_by_token(q, k, v, g, beta, s0)

    def step(s, x):
        o, s = delta_rule.gated_delta_step(*(a[None] for a in x), s[None])
        return s[0], o[0]

    s, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
    assert float(np.max(np.abs(np.asarray(o) - want_o))) < TOL
    assert float(np.max(np.abs(np.asarray(s) - want_s))) < TOL


def test_a_chunk_in_two_calls_is_the_chunk_in_one_and_padding_leaves_the_state():
    """What chunked prefill relies on: the state handed from call to call, and
    positions with g = 0 and beta = 0 (the engine's padding) changing nothing."""
    run = jax.jit(delta_rule.gated_delta_prefill)
    q, k, v, g, beta, s0 = _inputs(128, 2, 96, 192, seed=9)
    o, s = run(q, k, v, g, beta, s0)

    def part(lo, hi, state):
        """Tokens lo..hi as a call of 128 positions, the rest padding."""
        pad = lambda x: jnp.pad(x[lo:hi], ((0, 128 - (hi - lo)),) + ((0, 0),) * (x.ndim - 1))  # noqa: E731
        valid = (jnp.arange(128) < hi - lo)[:, None]
        out, state = run(pad(q), pad(k), pad(v), jnp.where(valid, pad(g), 0.0),
                         jnp.where(valid, pad(beta), 0.0), state)
        return out[: hi - lo], state

    o1, s1 = part(0, 70, s0)
    o2, s2 = part(70, 128, s1)
    assert float(jnp.max(jnp.abs(jnp.concatenate([o1, o2]) - o))) < TOL
    assert float(jnp.max(jnp.abs(s2 - s))) < TOL


def test_repeated_keys_do_not_blow_the_triangular_system_up():
    """64 times the same key with beta near 2 is what a product of powers of
    the system's matrix cannot survive; forward substitution can."""
    q, k, v, g, beta, s0 = _inputs(64, 1, 96, 192, seed=3)
    k = jnp.broadcast_to(k[:1], k.shape)
    beta = jnp.full_like(beta, 1.98)
    want_o, want_s = _token_by_token(q, k, v, g, beta, s0)
    o, s = delta_rule.gated_delta_prefill(q, k, v, g, beta, s0)
    scale = max(1.0, float(np.max(np.abs(want_s))))
    assert float(np.max(np.abs(np.asarray(o) - want_o))) < 1e-4 * scale
    assert float(np.max(np.abs(np.asarray(s) - want_s))) < 1e-4 * scale
