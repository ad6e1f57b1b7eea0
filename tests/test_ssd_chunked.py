"""``mamba2.ssd_chunked`` at Mamba-2's published A and dt draws.

Above a chunk's diagonal ``s_i - s_j`` is positive and grows with the
chunk's decay; exponentiating it before masking overflowed float32 and
made the gradient 0 * inf = NaN at A in [1, 16], dt in [1e-3, 0.1] and the
published chunk of 256.  The exponent is now masked first.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.models.mamba2 import ssd_chunked  # noqa: E402

BATCH, SEQ, HEADS, PDIM, STATE, CHUNK = 1, 512, 4, 8, 16, 256


def _inputs(A):
    k = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(k[0], (BATCH, SEQ, HEADS, PDIM))
    lo, hi = np.log(1e-3), np.log(1e-1)
    dt = jnp.exp(jax.random.uniform(k[1], (BATCH, SEQ, HEADS),
                                    minval=lo, maxval=hi))
    Bm = jax.random.normal(k[2], (BATCH, SEQ, STATE))
    Cm = jax.random.normal(k[3], (BATCH, SEQ, STATE))
    return x, dt, A, Bm, Cm


def _unmasked_exp(x, dt, A, Bm, Cm, chunk):
    """The former formula: exp of every s_i - s_j, masked afterwards."""
    Bsz, S, H, Pd = x.shape
    nc = S // chunk
    xc = x.reshape(Bsz, nc, chunk, H, Pd)
    dtc = dt.reshape(Bsz, nc, chunk, H)
    Bc, Cc = (m.reshape(Bsz, nc, chunk, -1) for m in (Bm, Cm))
    s = jnp.cumsum(dtc * A, axis=2)
    L = s[:, :, :, None, :] - s[:, :, None, :, :]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    L = jnp.where(causal[None, None, :, :, None], jnp.exp(L), 0.0)
    CB = jnp.einsum("bcqn,bckn->bcqk", Cc, Bc)
    M = CB[..., None] * L * dtc[:, :, None, :, :]
    return jnp.einsum("bcqkh,bckhp->bcqhp", M, xc).reshape(Bsz, S, H, Pd)


def test_gradients_finite_at_published_draws():
    A = -jnp.linspace(1.0, 16.0, HEADS)
    x, dt, A, Bm, Cm = _inputs(A)
    assert float(jnp.max(-jnp.sum(dt[0, :CHUNK] * A, axis=0))) > 88.0

    def loss(x, dt, A, Bm, Cm):
        return jnp.sum(jnp.sin(ssd_chunked(x, dt, A, Bm, Cm, CHUNK)))

    value, grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(
        x, dt, A, Bm, Cm)
    assert np.isfinite(float(value))
    for g in grads:
        assert bool(jnp.all(jnp.isfinite(g)))
    # the former formula overflows on these same inputs
    old = jax.grad(lambda dt: jnp.sum(jnp.sin(_unmasked_exp(
        x, dt, A, Bm, Cm, CHUNK))))(dt)
    assert not bool(jnp.all(jnp.isfinite(old)))


def test_values_at_unit_decay_equal_the_former_formula():
    """At A = 1 no exponent overflows; a single chunk of the sequence
    (no inter-chunk state) gives exactly what the former formula gave."""
    x, dt, A, Bm, Cm = _inputs(-jnp.ones((HEADS,)))
    new = ssd_chunked(x, dt, A, Bm, Cm, SEQ)
    old = _unmasked_exp(x, dt, A, Bm, Cm, SEQ)
    np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
