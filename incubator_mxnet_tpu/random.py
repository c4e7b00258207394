"""Random number handling: MXNet seed API over JAX threaded PRNG keys.

Parity target: [U:python/mxnet/random.py] + [U:include/mxnet/random_generator.h].
The reference keeps per-device RNG states inside the Resource manager; JAX is
functional, so we keep ONE process-level key that is split per sampling call
(eager mode), plus a stack of *traced* keys pushed by jitted callables
(hybridized blocks / train steps) so dropout & samplers stay deterministic and
trace-safe under ``jax.jit``.

``SPMDTrainer`` does not split this key every step: it draws ONE base key with
``get_key()`` and derives step ``t``'s key inside the compiled step as
``fold_in(base, t)``, so a step dispatches no PRNG program.  ``seed`` bumps
the thread's *seed epoch* (``seed_epoch()``, a host ``int``); a holder of such
a drawn-once key compares it and draws again after a re-seed.
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp

__all__ = ["seed", "seed_epoch", "get_key", "push_traced_key", "pop_traced_key", "uniform", "normal", "randint", "randn"]

_state = threading.local()


def _ensure():
    if not hasattr(_state, "key"):
        _state.key = jax.random.PRNGKey(0)
        _state.traced = []
        _state.epoch = 0
    return _state


def seed(seed_state, ctx="all"):
    """Parity: ``mx.random.seed``.  ``ctx`` accepted for API compat (JAX keys
    are device-agnostic)."""
    s = _ensure()
    s.key = jax.random.PRNGKey(int(seed_state))
    s.epoch += 1


def seed_epoch():
    """How many times this thread has called ``seed``.  Whoever keeps a key
    drawn once from ``get_key()`` (``SPMDTrainer``'s base key) compares
    this, an ``int`` and no device work, to see that the user re-seeded."""
    return _ensure().epoch


def get_key():
    """Split off a fresh PRNG key.  Inside a traced region this consumes the
    innermost traced key so the op is a pure function of the step seed."""
    s = _ensure()
    if s.traced:
        k, sub = jax.random.split(s.traced[-1])
        s.traced[-1] = k
        return sub
    s.key, sub = jax.random.split(s.key)
    return sub


def push_traced_key(key):
    _ensure().traced.append(key)


def pop_traced_key():
    return _ensure().traced.pop()


# -- mx.random sampling front-ends (return NDArray) -------------------------


def _wrap(data, ctx=None, out=None):
    from .ndarray.ndarray import NDArray

    arr = NDArray(data, ctx=ctx)
    if out is not None:
        out._data = arr._data
        out._version += 1
        return out
    return arr


def uniform(low=0, high=1, shape=(1,), dtype="float32", ctx=None, out=None):
    from .base import _as_np_dtype

    if isinstance(shape, int):
        shape = (shape,)
    data = jax.random.uniform(get_key(), shape, dtype=_as_np_dtype(dtype), minval=low, maxval=high)
    return _wrap(data, ctx, out)


def normal(loc=0, scale=1, shape=(1,), dtype="float32", ctx=None, out=None):
    from .base import _as_np_dtype

    if isinstance(shape, int):
        shape = (shape,)
    data = loc + scale * jax.random.normal(get_key(), shape, dtype=_as_np_dtype(dtype))
    return _wrap(data, ctx, out)


def randn(*shape, loc=0.0, scale=1.0, dtype="float32", ctx=None):
    return normal(loc, scale, shape or (1,), dtype=dtype, ctx=ctx)


def randint(low, high=None, shape=(1,), dtype="int32", ctx=None, out=None):
    from .base import _as_np_dtype

    if high is None:
        low, high = 0, low
    if isinstance(shape, int):
        shape = (shape,)
    data = jax.random.randint(get_key(), shape, low, high, dtype=_as_np_dtype(dtype))
    return _wrap(data, ctx, out)


def multinomial(data, shape=(1,), get_prob=False, dtype="int32", ctx=None):
    from .ndarray.ndarray import NDArray
    from .base import _as_np_dtype

    probs = data._data if isinstance(data, NDArray) else jnp.asarray(data)
    if isinstance(shape, int):
        shape = (shape,)
    logits = jnp.log(jnp.maximum(probs, 1e-30))
    n = 1
    for s in shape:
        n *= s
    if probs.ndim == 1:
        samples = jax.random.categorical(get_key(), logits, shape=(n,)).reshape(shape)
    else:
        samples = jax.random.categorical(get_key(), logits, axis=-1, shape=(n, probs.shape[0])).T
    out = _wrap(samples.astype(_as_np_dtype(dtype)), ctx)
    if get_prob:
        lp = jnp.take_along_axis(
            jnp.log(jnp.maximum(probs, 1e-30)).reshape(1, -1) if probs.ndim == 1 else logits,
            samples.reshape(-1, 1) if probs.ndim == 1 else samples,
            axis=-1,
        )
        return out, _wrap(lp.reshape(out.shape), ctx)
    return out


def shuffle(data, out=None):
    from .ndarray.ndarray import NDArray

    arr = data._data if isinstance(data, NDArray) else jnp.asarray(data)
    perm = jax.random.permutation(get_key(), arr.shape[0])
    return _wrap(arr[perm], getattr(data, "_ctx", None), out)


def gamma(alpha=1, beta=1, shape=(1,), dtype="float32", ctx=None, out=None):
    from .base import _as_np_dtype

    if isinstance(shape, int):
        shape = (shape,)
    data = jax.random.gamma(get_key(), alpha, shape, dtype=_as_np_dtype(dtype)) * beta
    return _wrap(data, ctx, out)


def exponential(scale=1.0, shape=(1,), dtype="float32", ctx=None, out=None):
    from .base import _as_np_dtype

    if isinstance(shape, int):
        shape = (shape,)
    data = scale * jax.random.exponential(get_key(), shape, dtype=_as_np_dtype(dtype))
    return _wrap(data, ctx, out)


def poisson(lam=1.0, shape=(1,), dtype="float32", ctx=None, out=None):
    from .base import _as_np_dtype

    if isinstance(shape, int):
        shape = (shape,)
    data = jax.random.poisson(get_key(), lam, shape).astype(_as_np_dtype(dtype))
    return _wrap(data, ctx, out)


def negative_binomial(k=1, p=1.0, shape=(1,), dtype="float32", ctx=None,
                      out=None):
    """Parity: ``mx.nd.random.negative_binomial`` — wraps the registered
    ``_random_negative_binomial`` sampler (gamma-Poisson mixture)."""
    from .ops.random_ops import _random_negative_binomial

    if not 0 < p <= 1:
        raise ValueError(f"negative_binomial requires 0 < p <= 1, got {p}")
    if k <= 0:
        raise ValueError(f"negative_binomial requires k > 0, got {k}")
    if isinstance(shape, int):
        shape = (shape,)
    data = _random_negative_binomial(k=k, p=p, shape=shape, dtype=dtype,
                                     key=get_key())
    return _wrap(data, ctx, out)


def generalized_negative_binomial(mu=1.0, alpha=1.0, shape=(1,),
                                  dtype="float32", ctx=None, out=None):
    """Parity: ``mx.nd.random.generalized_negative_binomial``."""
    from .ops.random_ops import _random_generalized_negative_binomial

    if mu <= 0 or alpha < 0:
        raise ValueError(
            f"generalized_negative_binomial requires mu > 0 and alpha >= 0, "
            f"got mu={mu}, alpha={alpha}")
    if isinstance(shape, int):
        shape = (shape,)
    data = _random_generalized_negative_binomial(mu=mu, alpha=alpha,
                                                 shape=shape, dtype=dtype,
                                                 key=get_key())
    return _wrap(data, ctx, out)
