"""The chunked scan's Pallas kernels (``ops/ssm.py``), in interpret mode,
against the XLA chunks and the step-by-step recurrence: y and the gradients of
all seven operands; the predicate ``_scan_path`` and the dispatch counters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu import profiler
from incubator_mxnet_tpu.ops import ssm
from incubator_mxnet_tpu.parallel import make_mesh

from test_nemotron_h import _naive_scan, _scan_operands

NAMES = ("x", "dt", "a_log", "b", "c", "d_skip", "dt_bias")

# length, chunk, groups, heads, batch, operand dtype
CASES = [
    (64, 16, 2, 4, 2, jnp.float32),     # four chunks, two heads a group
    (50, 16, 1, 4, 2, jnp.float32),     # padded inside: no chunk divides 50
    (32, 32, 4, 4, 1, jnp.float32),     # one chunk, a head a group
    (40, 16, 2, 8, 2, jnp.float32),     # padded, four heads a group
    (17, 4, 2, 4, 2, jnp.float32),      # padded, short chunks
    (64, 16, 2, 4, 2, jnp.bfloat16),    # the compute dtype under AMP
    (50, 16, 1, 8, 1, jnp.bfloat16),    # ... padded, eight heads a group
]
CASE_IDS = [f"s{s}-q{q}-g{g}-h{h}-b{b}-{jnp.dtype(t).name}" for s, q, g, h, b, t in CASES]


def operands(s, g, h, b, dtype, seed=0):
    o = _scan_operands(s, g, seed=seed, b=b, h=h)
    w = o.pop("weights")
    low = {k: (v.astype(dtype) if k in ("x", "dt", "b", "c") else v) for k, v in o.items()}
    return low, o, w


def scan(ops, chunk, monkeypatch, flash):
    monkeypatch.setenv("MXNET_TPU_FLASH", flash)
    return ssm.ssm_scan(*ops.values(), chunk_size=chunk, dt_floor=1e-4)


def grads(ops, w, chunk, monkeypatch, flash):
    monkeypatch.setenv("MXNET_TPU_FLASH", flash)
    loss = lambda *a: jnp.sum(ssm.ssm_scan(*a, chunk_size=chunk, dt_floor=1e-4).astype(jnp.float32) * w)
    return jax.grad(loss, argnums=tuple(range(7)))(*ops.values())


def apart(got, want):
    """The largest difference as a share of the largest value."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


def dispatched(before):
    after = profiler.counters()
    return tuple(after[n] - before[n] for n in ("ssm_scan_dispatch_pallas", "ssm_scan_dispatch_xla"))


@pytest.mark.parametrize("s,chunk,g,h,b,dtype", CASES, ids=CASE_IDS)
def test_scan_kernels_match_the_xla_chunks_and_the_recurrence(monkeypatch, s, chunk, g, h, b, dtype):
    """y: the kernels round where the XLA chunks round, so in the compute
    dtype they agree to a unit of it at most (float32: to its rounding of
    sums), and both lie within that dtype's rounding of the recurrence."""
    low, wide, _ = operands(s, g, h, b, dtype)
    before = profiler.counters()
    got = scan(low, chunk, monkeypatch, "interpret")
    assert dispatched(before) == (1, 0)
    xla = scan(low, chunk, monkeypatch, "off")
    assert dispatched(before) == (1, 1)
    assert got.dtype == xla.dtype == dtype and got.shape == xla.shape == wide["x"].shape
    want = _naive_scan(**wide, floor=1e-4)
    if dtype == jnp.float32:
        assert apart(got, xla) < 1e-6 and apart(got, want) < 5e-6
    else:
        assert apart(got, xla) <= 2.0 ** -8 and apart(got, want) < 0.03


@pytest.mark.parametrize("s,chunk,g,h,b,dtype", CASES, ids=CASE_IDS)
def test_scan_kernels_gradients_match_the_xla_chunks_and_the_recurrence(monkeypatch, s, chunk, g, h,
                                                                        b, dtype):
    """All seven operands' gradients by the backward kernel: float32 to the
    XLA form's autodiff within float32's rounding; in bf16 the two round the
    cotangents at other points, and each stays within bf16's rounding of the
    recurrence's float32 gradients."""
    low, wide, w = operands(s, g, h, b, dtype, seed=1)
    got = grads(low, w, chunk, monkeypatch, "interpret")
    xla = grads(low, w, chunk, monkeypatch, "off")
    want = jax.grad(lambda *a: jnp.sum(_naive_scan(*a, floor=1e-4) * w),
                    argnums=tuple(range(7)))(*wide.values())
    for name, k, x, r in zip(NAMES, got, xla, want):
        assert k.dtype == x.dtype and k.shape == x.shape, name
        if dtype == jnp.float32:
            assert apart(k, x) < 2e-5 and apart(k, r) < 2e-4, (name, apart(k, x), apart(k, r))
        else:
            assert apart(k, x) < 0.02 and apart(k, r) < 0.03, (name, apart(k, x), apart(k, r))


@pytest.mark.parametrize("flash,s,chunk,path", [
    ("interpret", 64, 16, "pallas"), ("interpret", 50, 16, "pallas"),
    ("auto", 64, 16, "xla"), ("off", 64, 16, "xla"), ("on", 64, 16, "xla")],
    ids=["interpreter", "interpreter-padded", "on-the-cpu", "kernels-off",
         "compiled-but-not-tiled"])
def test_scan_dispatch_is_counted_at_trace_time(monkeypatch, flash, s, chunk, path):
    """One count a traced call site, on the path the predicate chose, and a
    compiled program not traced, or counted, again; the jaxpr holds the
    kernel on the kernels' path only."""
    monkeypatch.setenv("MXNET_TPU_FLASH", flash)
    low, wide, _ = operands(s, 2, 4, 2, jnp.float32, seed=2)
    x, b = jnp.pad(low["x"], ((0, 0), (0, -s % chunk), (0, 0), (0, 0))), low["b"]
    assert (ssm._scan_path(x, b, chunk) is not None) == (path == "pallas")
    run = jax.jit(lambda *a: ssm.ssm_scan(*a, chunk_size=chunk, dt_floor=1e-4))
    before = profiler.counters()
    got = run(*low.values())
    counted = (1, 0) if path == "pallas" else (0, 1)
    assert dispatched(before) == counted
    run(*low.values())
    assert dispatched(before) == counted
    traced = str(jax.make_jaxpr(lambda *a: ssm.ssm_scan(*a, chunk_size=chunk, dt_floor=1e-4))(
        *low.values()))
    assert traced.count("pallas_call") == (path == "pallas")
    assert apart(got, _naive_scan(**wide, floor=1e-4)) < 5e-6


def test_compiled_scan_kernels_are_asked_only_for_what_mosaic_tiles(monkeypatch):
    """Off the interpreter the predicate takes the cell's shapes — 64 heads
    of 64 in 8 groups of state 128, chunk 128, bf16 — and wants bf16 operands,
    a chunk, a group's columns and a state that fill whole 128-lane columns,
    and blocks that fit VMEM; without a TPU, ``auto`` is the XLA chunks."""
    like = lambda s, h, p, g, n, dtype=jnp.bfloat16: (
        jax.ShapeDtypeStruct((1, s, h, p), dtype), jax.ShapeDtypeStruct((1, s, g, n), dtype))
    monkeypatch.setenv("MXNET_TPU_FLASH", "auto")
    assert ssm._scan_path(*like(8192, 64, 64, 8, 128), 128) is None          # a CPU
    monkeypatch.setenv("MXNET_TPU_FLASH", "on")
    launch = ssm._scan_path(*like(8192, 64, 64, 8, 128), 128)
    assert launch is not None and not launch.interpret and launch.mesh is None
    assert ssm._scan_path(*like(8192, 64, 64, 4, 128), 256) is not None      # 16 heads a group
    assert ssm._scan_path(*like(8192, 64, 64, 8, 128, jnp.float32), 128) is None
    assert ssm._scan_path(*like(8192, 64, 64, 8, 128), 64) is None           # a 64-lane chunk
    assert ssm._scan_path(*like(8192, 64, 64, 64, 128), 128) is None         # 64 columns a group
    assert ssm._scan_path(*like(8192, 64, 64, 8, 64), 128) is None           # a 64-lane state
    assert ssm._scan_path(*like(8192, 64, 64, 8, 128), 1024) is None         # VMEM
    assert ssm._scan_path(*like(8000, 64, 64, 8, 128), 128) is None          # no chunk divides


@pytest.mark.parametrize("axes,kernels", [({"dp": 2}, True), ({"dp": 2, "tp": 2}, False)],
                         ids=["dp2", "dp2-tp2"])
def test_scan_kernels_are_placed_on_the_mesh_of_the_trace(monkeypatch, axes, kernels):
    """Under a mesh that splits the batch alone the kernels are launched in a
    ``shard_map`` over its rows; a mesh that splits the model takes the XLA
    chunks: no compiler partitions a Mosaic kernel."""
    from incubator_mxnet_tpu.parallel import mesh_scope

    n = int(np.prod(list(axes.values())))
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")
    monkeypatch.setenv("MXNET_TPU_FLASH", "interpret")
    mesh = make_mesh(devices=jax.devices()[:n], **axes)
    low, _, w = operands(32, 2, 4, 2, jnp.float32, seed=3)
    loss = lambda *a: jnp.sum(ssm.ssm_scan(*a, chunk_size=16, dt_floor=1e-4) * w)
    want = jax.grad(loss, argnums=tuple(range(7)))(*low.values())

    def scoped(*a):
        with mesh_scope(mesh):
            return jax.grad(loss, argnums=tuple(range(7)))(*a)

    traced = str(jax.make_jaxpr(scoped)(*low.values()))
    assert traced.count("pallas_call") == (2 if kernels else 0)
    assert traced.count("shard_map") == (2 if kernels else 0)
    for name, g, r in zip(NAMES, jax.jit(scoped)(*low.values()), want):
        assert apart(g, r) < 2e-5, name
