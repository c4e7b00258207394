"""The COMPILED scan kernels (``ops/ssm.py``) on the chip at the nemotron
cell's shape — 64 heads of 64 in 8 groups of state 128 at S 8192, chunk 128,
bf16 operands as the mixer hands them over under AMP — against the XLA chunks
on the same chip, y and the gradients of all seven operands, each as an rms
share; and both against the XLA chunks in float32 at ``Precision.HIGHEST``,
so that the kernels are held to the XLA form's own distance from float32.
The cell's reference check reads only the forward's logits: this is what
guards the backward on the chip.

    MXNET_TEST_CTX=tpu python -m pytest tpu_tests/test_ssm_scan_tpu.py -q
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu import profiler
from incubator_mxnet_tpu.ops import ssm

B, S, H, P, G, N, CHUNK = 1, 8192, 64, 64, 8, 128, 128
NAMES = ("x", "dt", "a_log", "b", "c", "d_skip", "dt_bias")


def operands(seed):
    """The mixer's operands at the model's initialisation: x̃, B, C after a
    SiLU, the raw step from a projection, ``A_log = log U(1, 16)``, ``dt_bias``
    the inverse softplus of a log-uniform step in [0.001, 0.1], ``D = 1``."""
    rng = np.random.default_rng(seed)
    silu = lambda a: a / (1 + np.exp(-a))
    step = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), H))
    ops = (silu(rng.standard_normal((B, S, H, P))),
           rng.standard_normal((B, S, H)) * 0.5,
           np.log(rng.uniform(1, 16, H)),
           silu(rng.standard_normal((B, S, G, N))),
           silu(rng.standard_normal((B, S, G, N))),
           np.ones(H),
           step + np.log(-np.expm1(-step)))
    low = (0, 1, 3, 4)            # in the compute dtype, as the mixer hands them over
    return tuple(jnp.asarray(a, jnp.bfloat16 if i in low else jnp.float32)
                 for i, a in enumerate(ops)), jnp.asarray(rng.standard_normal((B, S, H, P)), jnp.float32)


def value_and_grads(ops, w, path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_FLASH", "auto" if path == "kernels" else "off")

    def loss(*a):
        y = ssm.ssm_scan(*a, chunk_size=CHUNK, dt_floor=1e-4)
        return jnp.sum(y.astype(jnp.float32) * w), y

    fn = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(7)), has_aux=True))
    (_, y), grads = fn(*ops)
    return [np.asarray(a, np.float64) for a in (y,) + tuple(grads)]


def apart(got, want):
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want ** 2)))


@pytest.mark.parametrize("seed", [0, 1])
def test_compiled_kernels_match_the_xla_chunks_at_the_cells_shape(seed, monkeypatch):
    ops, w = operands(seed)
    before = profiler.counters()
    kernels = value_and_grads(ops, w, "kernels", monkeypatch)
    after = profiler.counters()
    assert after["ssm_scan_dispatch_pallas"] - before["ssm_scan_dispatch_pallas"] == 1
    assert after["ssm_scan_dispatch_xla"] == before["ssm_scan_dispatch_xla"]
    xla = value_and_grads(ops, w, "xla", monkeypatch)
    wide = tuple(a.astype(jnp.float32) for a in ops)
    f32 = value_and_grads(wide, w, "xla", monkeypatch)
    for name, k, x, f in zip(("y",) + NAMES, kernels, xla, f32):
        # the same roundings in another order: a few bf16 units apart at most,
        # and no further from float32 than the XLA chunks are
        assert apart(k, x) < (4e-3 if name == "y" else 1.5e-2), (name, apart(k, x))
        assert apart(k, f) <= 1.5 * apart(x, f) + 2e-4, (name, apart(k, f), apart(x, f))
