"""``GenerationServer`` — autoregressive decode with iteration-level
continuous batching over a device-resident slot KV cache.

`InferenceServer` (ISSUE 8) sells exactly one product: a single forward
per request.  The workload that dominates consumer inference —
autoregressive decode, hundreds of sequential steps per request — has a
different shape entirely (the Gemma-on-Cloud-TPU serving setup in
PAPERS.md): a request's *lifetime* spans many device dispatches, so
batching whole requests ("drain and refill") lets chip utilization bleed
away as the batch empties — every finished sequence leaves its lane idle
until the LAST one finishes.  The fix is **iteration-level continuous
batching** (Orca; vLLM): the scheduler revisits membership *between
decode steps* — finished sequences leave immediately, queued prefills
join into the freed KV-cache slots — so the decode batch stays full under
load and tokens/sec-at-SLO stops being bounded by the longest request in
each wave.

The steady-state loop is compile-free by construction:

* the KV cache is a fixed ladder of :class:`~.kv_cache.SlotKVCache`
  pools (``serving/kv_cache.py``) — every decode program is shaped by a
  POOL, never by traffic;
* prefill pads prompts up to the existing :class:`~.bucketing.ShapeBucketer`
  length ladder (one compiled encoder program per bucket, masked so
  padding cannot leak into the memory the decode steps attend to);
* join/leave is host-side slot indexing plus ONE compiled
  memory-insert dispatch — nothing about membership is a trace input;
* every program compiles in ``start()`` under
  ``profiler.compile_site("generation.warmup")`` and the steady-state
  compile guard is armed on exit, so with ``MXNET_COMPILE_GUARD=raise``
  a single stray recompile fails loudly (and is enforced by test and by
  the ``benchmark/opperf/generation.py`` CI smoke).

On top of the loop: a **streaming token surface** (each ``submit()``
returns a :class:`GenerationResult` whose ``stream()`` iterator — or
``on_token`` callback — yields tokens as they decode; ``cancel()`` frees
the slot at the next iteration boundary) and **multi-tenant admission
control** (named tenants with per-tenant queue caps, slot caps and
TTFT/TPOT SLOs; queue-depth load shedding raises :class:`AdmissionError`
at ``submit()`` so overload degrades by rejecting, not by blowing every
tenant's latency).  Several ``GenerationServer``s (different models /
checkpoints) can share one device — each registers its own metrics
provider, so one Prometheus scrape carries every tenant of every server.

Dispatch substrate: :class:`~..predictor.StatefulExecutor` — the decode
step consumes and re-produces the cache buffers (donated, so steady-state
HBM holds one copy), and the executor reports any post-warmup compile
into the PR 9 registry with full signature attribution.
"""
from __future__ import annotations

import itertools
import math
import threading
import time
from collections import deque

import numpy as _np

from .. import profiler
from ..predictor import StatefulExecutor
from .bucketing import ShapeBucketer
from .kv_cache import KVCacheLadder
from .server import ServerDrainingError

__all__ = ["GenerationServer", "GenerationResult", "AdmissionError",
           "Tenant"]

_perf = time.perf_counter
_env_int = profiler._env_int
_env_float = profiler._env_float

_name_lock = threading.Lock()
_name_seq = 0


def _default_name():
    """Unique per-process default provider key (the ``io_pipeline``
    rule): a second default-named server must not silently replace the
    first's gauges, and closing one must not unregister the survivor's.
    The first server keeps the stable name ``generation``."""
    global _name_seq
    with _name_lock:
        _name_seq += 1
        n = _name_seq
    return "generation" if n == 1 else f"generation{n}"


class AdmissionError(RuntimeError):
    """Raised by ``submit()`` when admission control sheds the request
    (tenant queue over its depth cap).  Callers should back off — the
    server is protecting the latency of requests already admitted."""


class Tenant:
    """Admission/SLO policy for one tenant.

    Parameters
    ----------
    name : tenant key (``submit(..., tenant=name)``).
    max_queue : queue-depth cap — submissions past it are SHED with
        :class:`AdmissionError` (env ``MXNET_GEN_MAX_QUEUE``, 64).
    max_slots : cap on concurrently decoding slots this tenant may hold
        (None = no cap) — a noisy neighbor cannot monopolize the cache.
    slo_ttft_ms : time-to-first-token SLO (env ``MXNET_GEN_TTFT_SLO_MS``,
        1000).
    slo_tpot_ms : per-output-token SLO (env ``MXNET_GEN_TPOT_SLO_MS``,
        200).
    """

    def __init__(self, name, max_queue=None, max_slots=None,
                 slo_ttft_ms=None, slo_tpot_ms=None):
        self.name = str(name)
        self.max_queue = int(max_queue if max_queue is not None
                             else _env_int("MXNET_GEN_MAX_QUEUE", 64))
        self.max_slots = None if max_slots is None else int(max_slots)
        self.slo_ttft_ms = float(
            slo_ttft_ms if slo_ttft_ms is not None
            else _env_float("MXNET_GEN_TTFT_SLO_MS", 1000.0))
        self.slo_tpot_ms = float(
            slo_tpot_ms if slo_tpot_ms is not None
            else _env_float("MXNET_GEN_TPOT_SLO_MS", 200.0))
        # live accounting (under the server lock)
        self.submitted = 0
        self.shed = 0
        self.completed = 0
        self.cancelled = 0
        self.failed = 0
        self.tokens = 0
        self.slo_violations = 0
        self.active_slots = 0

    def stats(self):
        return {
            "max_queue": self.max_queue,
            "max_slots": self.max_slots,
            "slo_ttft_ms": self.slo_ttft_ms,
            "slo_tpot_ms": self.slo_tpot_ms,
            "submitted": self.submitted,
            "shed": self.shed,
            "completed": self.completed,
            "cancelled": self.cancelled,
            "failed": self.failed,
            "tokens": self.tokens,
            "slo_violations": self.slo_violations,
            "active_slots": self.active_slots,
        }


class GenerationResult:
    """Streaming handle for one generation request.

    Tokens arrive as the decode loop emits them: iterate (``for tok in
    res.stream():``), poll (``tokens_so_far()``), or block for the full
    sequence (``result()``).  ``cancel()`` asks the scheduler to free the
    request's slot at the next iteration boundary — a disconnected
    client must release its cache slot, not decode to max length for
    nobody."""

    def __init__(self, request_id, tenant):
        self.request_id = request_id
        self.tenant = tenant
        self.finish_reason = None      # "eos" | "length" | "cancelled" | "error"
        self.ttft_ms = None
        self.tpot_ms = None
        self._tokens = []
        self._token_times = []
        self._cond = threading.Condition()
        self._done = False
        self._exc = None
        self._cancel = False

    # -- consumer surface ----------------------------------------------
    def done(self):
        return self._done

    def cancelled(self):
        return self._cancel

    def cancel(self):
        """Request cancellation (idempotent; safe from any thread).  The
        slot is freed at the next iteration boundary; ``finish_reason``
        becomes ``"cancelled"`` unless the request already finished."""
        with self._cond:
            self._cancel = True
            self._cond.notify_all()

    def tokens_so_far(self):
        with self._cond:
            return list(self._tokens)

    def stream(self, timeout=60.0):
        """Yield token ids as they decode; returns when the request
        finishes (raises what the scheduler raised on error).  ``timeout``
        bounds the wait for EACH token."""
        i = 0
        while True:
            with self._cond:
                while len(self._tokens) <= i and not self._done:
                    if not self._cond.wait(timeout):
                        raise TimeoutError(
                            f"request {self.request_id!r}: no token within "
                            f"{timeout}s")
                if len(self._tokens) > i:
                    tok = self._tokens[i]
                else:  # done
                    if self._exc is not None:
                        raise self._exc
                    return
            yield tok
            i += 1

    def result(self, timeout=60.0):
        """Block until finished; returns the generated token ids as a
        numpy int32 array (includes the closing ``eos`` when the model
        produced one — ``finish_reason`` tells which)."""
        with self._cond:
            if not self._cond.wait_for(lambda: self._done, timeout):
                raise TimeoutError(
                    f"request {self.request_id!r} not finished in {timeout}s")
            if self._exc is not None:
                raise self._exc
            return _np.asarray(self._tokens, _np.int32)

    # -- scheduler side ------------------------------------------------
    def _push(self, token, now):
        with self._cond:
            self._tokens.append(int(token))
            self._token_times.append(now)
            self._cond.notify_all()

    def _finish(self, reason, t_submit, exc=None):
        with self._cond:
            if self._done:
                return
            self.finish_reason = reason
            self._exc = exc
            if self._token_times:
                self.ttft_ms = (self._token_times[0] - t_submit) * 1e3
                if len(self._token_times) > 1:
                    self.tpot_ms = ((self._token_times[-1]
                                     - self._token_times[0])
                                    / (len(self._token_times) - 1)) * 1e3
            self._done = True
            self._cond.notify_all()


class _GenRequest:
    __slots__ = ("rid", "tenant", "prompt", "prompt_bucket", "max_new",
                 "on_token", "t_submit", "result", "pool", "slot")

    def __init__(self, rid, tenant, prompt, prompt_bucket, max_new,
                 on_token, t_submit):
        self.rid = rid
        self.tenant = tenant
        self.prompt = prompt
        self.prompt_bucket = prompt_bucket
        self.max_new = max_new
        self.on_token = on_token
        self.t_submit = t_submit
        self.result = GenerationResult(rid, tenant.name)
        self.pool = None
        self.slot = None


# ---------------------------------------------------------------------------
# model adapter: pure jitted programs from a Transformer
# ---------------------------------------------------------------------------


class _TransformerAdapter:
    """Pure prefill / decode-step / memory-insert programs over a
    :class:`~..gluon.model_zoo.transformer.Transformer`.

    Prefill = masked encoder over the bucket-padded prompt + each decoder
    layer's cross-attention KV projection, padded out to the memory
    width (so one insert program per pool serves every prompt bucket).
    Decode = one position for EVERY slot of a pool: per-slot positions,
    per-slot self-attention over the slot's cache rows, per-slot
    ``mem_len``-masked cross-attention — slots are fully independent, so
    a request decodes identically whatever else shares the batch (the
    continuous-batching equivalence contract, enforced by test)."""

    def __init__(self, model):
        cells = model.decoder._layers
        if not all(getattr(c, "_pre_norm", False) for c in cells):
            raise NotImplementedError(
                "GenerationServer requires a pre-norm decoder")
        enc_cells = model.encoder._layers
        if not all(getattr(c, "_pre_norm", False) for c in enc_cells):
            raise NotImplementedError(
                "GenerationServer requires a pre-norm encoder")
        self.model = model
        self.enc_cells = enc_cells
        self.dec_cells = cells
        self.layers = len(cells)
        self.units = model._units
        self.vocab = model._vocab
        self.heads = cells[0].self_attention._num_heads
        self.head_dim = self.units // self.heads
        self.pos_table = model.pos_enc._table      # numpy [max_len, units]
        self.max_positions = int(self.pos_table.shape[0])
        self.params = sorted(model.collect_params().values(),
                             key=lambda p: p.name)
        if any(p._data is None for p in self.params):
            raise ValueError(
                "model parameters are uninitialized/deferred — run one "
                "forward (or load a checkpoint) before binding a "
                "GenerationServer")
        # The server runs where its KV cache lands — the process's default
        # device — and the weights ride every program as ARGUMENTS (not
        # closed-over constants: XLA would bake a copy of them into each
        # bucket's program and constant-fold over it).  Committed
        # arguments decide where a program runs, so place them here:
        # ``net.initialize()`` with no ctx commits parameters to the host
        # CPU, and a TPU host would otherwise decode there without a word.
        import jax
        import jax.numpy as jnp

        self.device = next(iter(jnp.zeros(()).devices()))
        self.param_arrays = jax.device_put(
            [p._data._data for p in self.params], self.device)
        self.dtype = self.param_arrays[0].dtype

    def _attend(self, q, k, v, mask):
        """q [S,1,H,dh]; k/v [S,Tk,H,dh]; mask [S,Tk] bool → [S,1,units]."""
        import jax
        import jax.numpy as jnp

        scale = 1.0 / math.sqrt(self.head_dim)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32).astype(v.dtype)
        return out.reshape(out.shape[0], 1, self.units)

    def make_prefill(self, prompt_bucket, mem_width):
        """Program: (params, src [1, Lb] int32, src_len 0-d) → (mem_k, mem_v)
        each [layers, 1, mem_width, H, dh].  The encoder self-attention
        masks keys past ``src_len``, so the first ``src_len`` memory rows
        are computed exactly as an unpadded encode would (pad rows emit
        garbage that the decode-side ``mem_len`` mask never reads)."""
        import jax.numpy as jnp

        from ..gluon.block import traced_params
        from ..ndarray.ndarray import NDArray

        model, units, H, dh = self.model, self.units, self.heads, self.head_dim
        Lb = int(prompt_bucket)
        pos = jnp.asarray(self.pos_table[:Lb])

        def pure(state, inputs):
            src, src_len = inputs["src"], inputs["src_len"]
            with traced_params(self.params, inputs["params"]):
                x = model.embed(NDArray(src))._data * math.sqrt(units)
                x = x + pos[None].astype(x.dtype)
                valid = jnp.arange(Lb) < src_len            # [Lb] keys
                for cell in self.enc_cells:
                    h = cell.ln_attn(NDArray(x))._data
                    qkv = cell.attention.qkv(NDArray(h))._data
                    qkv = qkv.reshape(1, Lb, 3, H, dh)
                    x = x + cell.attention.out_proj(
                        NDArray(self._attend_full(qkv, valid)))._data
                    h = cell.ln_ffn(NDArray(x))._data
                    x = x + cell.ffn(NDArray(h))._data
                mem = NDArray(x)
                mks, mvs = [], []
                for cell in self.dec_cells:
                    kv = cell.cross_attention.kv_proj(mem)._data
                    kv = kv.reshape(1, Lb, 2, H, dh)
                    mks.append(kv[:, :, 0])
                    mvs.append(kv[:, :, 1])
            mem_k = jnp.stack(mks)                      # [L, 1, Lb, H, dh]
            mem_v = jnp.stack(mvs)
            pad = int(mem_width) - Lb
            if pad > 0:
                widths = ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))
                mem_k = jnp.pad(mem_k, widths)
                mem_v = jnp.pad(mem_v, widths)
            return (mem_k, mem_v), state

        return pure

    def _attend_full(self, qkv, valid):
        """Encoder self-attention at full width: qkv [1,Lb,3,H,dh], valid
        [Lb] key mask → [1, Lb, units]."""
        import jax
        import jax.numpy as jnp

        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        scale = 1.0 / math.sqrt(self.head_dim)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[None, None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32).astype(v.dtype)
        return out.reshape(1, -1, self.units)

    def make_decode(self, slots, bucket, mem_width):
        """Program: state {self_k, self_v, mem_k, mem_v} + inputs
        (params, tok [S], pos [S], mem_len [S]) → (logits [S, V], new state).
        Writes each slot's K/V at its own position, then attends ``<=
        pos`` — write-before-read is what lets ``free()`` skip clearing
        device rows."""
        import jax.numpy as jnp

        from ..gluon.block import traced_params
        from ..ndarray.ndarray import NDArray

        model, units, H, dh = self.model, self.units, self.heads, self.head_dim
        S, T, Sm = int(slots), int(bucket), int(mem_width)
        pos_table = jnp.asarray(self.pos_table)

        def pure(state, inputs):
            tok, pos, mem_len = inputs["tok"], inputs["pos"], inputs["mem_len"]
            self_k, self_v = state["self_k"], state["self_v"]
            mem_k, mem_v = state["mem_k"], state["mem_v"]
            rows = jnp.arange(S)
            valid_self = jnp.arange(T)[None, :] <= pos[:, None]     # [S,T]
            valid_mem = jnp.arange(Sm)[None, :] < mem_len[:, None]  # [S,Sm]
            with traced_params(self.params, inputs["params"]):
                x = model.embed(NDArray(tok.reshape(S, 1)))._data \
                    * math.sqrt(units)
                x = x + jnp.take(pos_table, pos, axis=0)[:, None, :] \
                    .astype(x.dtype)
                new_k, new_v = [], []
                for l, cell in enumerate(self.dec_cells):
                    h = cell.ln_self(NDArray(x))._data
                    qkv = cell.self_attention.qkv(NDArray(h))._data
                    qkv = qkv.reshape(S, 1, 3, H, dh)
                    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
                    ck = self_k[l].at[rows, pos].set(
                        k[:, 0].astype(self_k.dtype))
                    cv = self_v[l].at[rows, pos].set(
                        v[:, 0].astype(self_v.dtype))
                    new_k.append(ck)
                    new_v.append(cv)
                    out = self._attend(q, ck, cv, valid_self)
                    x = x + cell.self_attention.out_proj(NDArray(out))._data
                    h = cell.ln_cross(NDArray(x))._data
                    q2 = cell.cross_attention.q_proj(NDArray(h))._data
                    q2 = q2.reshape(S, 1, H, dh)
                    out2 = self._attend(q2, mem_k[l], mem_v[l], valid_mem)
                    x = x + cell.cross_attention.out_proj(NDArray(out2))._data
                    h = cell.ln_ffn(NDArray(x))._data
                    x = x + cell.ffn(NDArray(h))._data
                if model._tie:
                    logits = jnp.einsum(
                        "bqd,vd->bqv", x,
                        model.embed.weight.data()._data.astype(x.dtype))
                else:
                    logits = model.proj(NDArray(x))._data
            new_state = {"self_k": jnp.stack(new_k),
                         "self_v": jnp.stack(new_v),
                         "mem_k": mem_k, "mem_v": mem_v}
            return logits[:, 0], new_state

        return pure

    def make_insert(self):
        """Program: write one request's prefill product into a slot's
        memory rows (``slot`` is a traced scalar — joining slot 3 vs slot
        5 is the SAME program)."""
        from jax import lax

        def pure(state, inputs):
            slot = inputs["slot"]
            mk = inputs["mem_k"].astype(state["mem_k"].dtype)
            mv = inputs["mem_v"].astype(state["mem_v"].dtype)
            mem_k = lax.dynamic_update_slice(state["mem_k"], mk,
                                             (0, slot, 0, 0, 0))
            mem_v = lax.dynamic_update_slice(state["mem_v"], mv,
                                             (0, slot, 0, 0, 0))
            return (), {"self_k": state["self_k"], "self_v": state["self_v"],
                        "mem_k": mem_k, "mem_v": mem_v}

        return pure


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------


class GenerationServer:
    """Continuous-batching autoregressive generation over a Transformer.

    Parameters
    ----------
    model : a pre-norm ``gluon.model_zoo.transformer.Transformer`` with
        materialized parameters (run one forward first).  The server
        treats the weights as frozen from ``start()`` to ``close()``.
    bos, eos : special token ids (decode primes with ``bos``; a sampled
        ``eos`` finishes the request).
    max_prompt_length / prompt_buckets : prompt ladder
        (:class:`ShapeBucketer` semantics; ``max_prompt_length`` is also
        the submit-time admission ceiling).
    max_new_tokens / decode_buckets : decode-length ladder for the KV
        pools; ``max_new_tokens`` is the per-request default and ceiling.
    slots_per_bucket : pool capacity (int or ``{bucket: n}``; env
        ``MXNET_GEN_SLOTS``, 4).
    tenants : ``{name: dict(max_queue=, max_slots=, slo_ttft_ms=,
        slo_tpot_ms=)}`` — a ``"default"`` tenant with env-default policy
        is always present.
    batching : ``"continuous"`` (default — join between iterations) or
        ``"static"`` (drain-and-refill: admissions only when the decode
        batch is EMPTY; the benchmark's ablation baseline).
    max_prefills_per_iter : prefill budget per iteration boundary — caps
        how long a join wave may stall decoding for requests already in
        flight (env ``MXNET_GEN_MAX_PREFILLS``, 2).
    greedy argmax is the sampling rule (the equivalence contract); the
    streaming surface and slot lifecycle are sampling-agnostic.
    """

    def __init__(self, model, *, bos, eos, max_prompt_length=None,
                 prompt_buckets=None, max_new_tokens=None,
                 decode_buckets=None, slots_per_bucket=None, tenants=None,
                 batching="continuous", max_prefills_per_iter=None,
                 memory_budget=None, name=None, warmup=True, autostart=True):
        if batching not in ("continuous", "static"):
            raise ValueError(f"batching must be 'continuous' or 'static', "
                             f"got {batching!r}")
        # memory_budget: a profiler.MemoryBudget slot admission consults —
        # while it reports pressure, queued prefills DEFER (requeued at
        # the front, memory_budget_refusal counts) instead of pushing the
        # device into RESOURCE_EXHAUSTED mid-decode.  The gate is OPT-IN:
        # an explicit budget object, or the process budget while
        # MXNET_MEM_BUDGET_MB is set (checked per admission — the env
        # limit is dynamic) — a serving deployment sized to legitimately
        # fill HBM past the pressure fraction must not have every
        # admission deferred by default.
        self._budget_explicit = memory_budget is not None
        self._budget = (memory_budget if memory_budget is not None
                        else profiler.memory_budget())
        self.bos, self.eos = int(bos), int(eos)
        self.name = str(name) if name is not None else _default_name()
        self.batching = batching
        self._adapter = _TransformerAdapter(model)
        self._prompt_bucketer = ShapeBucketer(
            buckets=prompt_buckets, max_length=max_prompt_length)
        self.max_new_tokens = int(
            max_new_tokens if max_new_tokens is not None
            else _env_int("MXNET_GEN_MAX_NEW_TOKENS", 64))
        slots = (slots_per_bucket if slots_per_bucket is not None
                 else _env_int("MXNET_GEN_SLOTS", 4))
        self._ladder = KVCacheLadder(
            self._adapter.layers, self._adapter.heads,
            self._adapter.head_dim,
            mem_width=self._prompt_bucketer.buckets[-1],
            buckets=decode_buckets, max_length=self.max_new_tokens,
            slots_per_bucket=slots, dtype=self._adapter.dtype)
        top = max(self._ladder.buckets[-1],
                  self._prompt_bucketer.buckets[-1])
        if top > self._adapter.max_positions:
            raise ValueError(
                f"ladder top {top} exceeds the model's positional table "
                f"({self._adapter.max_positions} positions)")
        self.max_prefills_per_iter = int(
            max_prefills_per_iter if max_prefills_per_iter is not None
            else _env_int("MXNET_GEN_MAX_PREFILLS", 2))

        # -- tenants -----------------------------------------------------
        self.tenants = {}
        for tname, cfg in (tenants or {}).items():
            self.tenants[str(tname)] = Tenant(tname, **dict(cfg))
        self.tenants.setdefault("default", Tenant("default"))
        self._queues = {t: deque() for t in self.tenants}
        self._rr = list(self.tenants)      # round-robin admission order

        # -- executors (programs bound here, compiled in start()) --------
        import jax

        self._prefill_exe = StatefulExecutor(
            {}, name="generation_prefill", compile_site="generation.prefill")
        mem_w = self._prompt_bucketer.buckets[-1]
        for lb in self._prompt_bucketer.buckets:
            self._prefill_exe.add_program(
                f"prefill_{lb}", self._adapter.make_prefill(lb, mem_w))
        self._exes = {}
        for b, pool in self._ladder.pools.items():
            # committed like the weights: a cache that starts uncommitted
            # and comes back committed from its first decode would change
            # the insert program's signature after warmup
            exe = StatefulExecutor(
                jax.device_put(pool.state, self._adapter.device),
                name=f"generation_decode_{b}",
                compile_site="generation.decode")
            pool.state = None     # ownership transfers: the donated buffers
                                  # now live in (and only in) the executor
            exe.add_program("decode",
                            self._adapter.make_decode(pool.slots, b, mem_w))
            exe.add_program("insert", self._adapter.make_insert())
            self._exes[b] = exe

        # -- scheduler state --------------------------------------------
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._rid = itertools.count(1)   # next() is atomic: no lock
        self._started = False
        self._closing = False
        self._closed = False
        self._drain = True
        self._thread = None
        self._do_warmup = bool(warmup)
        self._iterations = 0
        self._n_completed = 0
        self._ttfts = deque(maxlen=2048)
        self._tpots = deque(maxlen=2048)
        self._queue_waits = deque(maxlen=2048)   # ms, admission - submit
        self._tok_window = deque(maxlen=4096)    # (t_emit,) for tokens/sec
        if autostart:
            self.start()

    @property
    def param_arrays(self):
        """The frozen weights as placed on the serving device — what every
        prefill/decode program receives as its ``params`` argument."""
        return self._adapter.param_arrays

    # ------------------------------------------------------------------
    def start(self):
        """Compile every program (prefill per prompt bucket; decode +
        insert per pool), arm the steady-state compile guard, start the
        scheduler thread, register the metrics provider.  Idempotent."""
        with self._lock:
            if self._started:
                return self
            if self._closed:
                raise RuntimeError("server is closed")
            self._started = True
        if self._do_warmup:
            with profiler.span(
                    "generation.warmup", "serving",
                    {"prompt_buckets": list(self._prompt_bucketer.buckets),
                     "pools": list(self._exes)}), \
                    profiler.compile_site("generation.warmup"), \
                    profiler.compile_guard_paused():
                warm_mem = None
                for lb in self._prompt_bucketer.buckets:
                    src = _np.zeros((1, lb), _np.int32)
                    warm_mem = self._prefill_exe.run(
                        f"prefill_{lb}", params=self.param_arrays,
                        src=src, src_len=_np.int32(1))
                mk, mv = warm_mem
                for b, exe in self._exes.items():
                    pool = self._ladder.pools[b]
                    exe.run("insert", slot=_np.int32(0), mem_k=mk, mem_v=mv)
                    exe.run("decode", params=self.param_arrays,
                            tok=_np.zeros(pool.slots, _np.int32),
                            pos=_np.zeros(pool.slots, _np.int32),
                            mem_len=_np.ones(pool.slots, _np.int32))
            # the program set is closed and compiled: any further compile
            # is a steady-state violation (MXNET_COMPILE_GUARD escalates)
            profiler.arm_compile_guard("generation")
        self._thread = threading.Thread(
            target=self._loop, name=f"mxtpu-{self.name}-scheduler",
            daemon=True)
        self._thread.start()
        profiler.register_metrics_provider(self.name, self._provider)
        return self

    # -- submission ----------------------------------------------------
    def submit(self, prompt, *, tenant="default", max_new_tokens=None,
               on_token=None, request_id=None):
        """Enqueue one prompt (1-D int token array) and return its
        :class:`GenerationResult`.

        Raises synchronously — a request that can never be served, or
        that admission control sheds, must fail at the door:

        * ``ValueError`` — prompt longer than the prompt ladder's
          ``max_length`` ceiling, or ``max_new_tokens`` past the decode
          ladder (clear submit-time errors, never a scheduler-thread
          failure);
        * :class:`AdmissionError` — the tenant's queue is at
          ``max_queue`` (load shedding; ``generation_shed`` counts).
        """
        prompt = _np.asarray(prompt, _np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must contain at least one token")
        if prompt.size > self._prompt_bucketer.max_length:
            raise ValueError(
                f"prompt length {prompt.size} exceeds max_prompt_length "
                f"{self._prompt_bucketer.max_length} — rejected at submit "
                f"(buckets: {list(self._prompt_bucketer.buckets)})")
        pb = self._prompt_bucketer.bucket_for(prompt.size)
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else self.max_new_tokens)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if max_new > self._ladder.max_length:
            raise ValueError(
                f"max_new_tokens {max_new} exceeds the decode ladder "
                f"ceiling {self._ladder.max_length} — rejected at submit")
        ten = self.tenants.get(str(tenant))
        if ten is None:
            raise ValueError(f"unknown tenant {tenant!r}; tenants are "
                             f"{sorted(self.tenants)}")
        t0 = _perf()
        # the id is drawn before the span opens so that both sinks carry it
        # (a shed request leaves a gap in the sequence)
        rid = request_id if request_id is not None else next(self._rid)
        with profiler.span("generation.enqueue", "serving",
                           {"request": rid, "tenant": ten.name,
                            "prompt_bucket": pb, "max_new": max_new}), \
                self._cond:
            if self._closing or self._closed:
                raise ServerDrainingError(
                    "server is draining/closed — retry against another "
                    "replica")
            if not self._started:
                raise RuntimeError("server is not started")
            q = self._queues[ten.name]
            if len(q) >= ten.max_queue:
                ten.shed += 1
                profiler.incr("generation_shed")
                raise AdmissionError(
                    f"tenant {ten.name!r} queue at max_queue="
                    f"{ten.max_queue} — request shed (back off)")
            req = _GenRequest(rid, ten, prompt, pb, max_new, on_token, t0)
            q.append(req)
            ten.submitted += 1
            self._cond.notify_all()
        profiler.incr("generation_request")
        return req.result

    def generate(self, prompt, timeout=120.0, **kw):
        """Blocking convenience: ``submit(...).result(timeout)``."""
        return self.submit(prompt, **kw).result(timeout)

    # -- scheduler -----------------------------------------------------
    def _runnable_locked(self):
        """True when an iteration can make progress: live slots to
        decode, or a queued request its tenant could actually admit.  A
        queue whose every tenant is slot-capped out is NOT runnable —
        spinning on it would burn a core without advancing anything
        (when nothing is active every slot is free, so capacity can
        never be the blocker here)."""
        if self._ladder.n_active > 0:
            return True
        for tname, q in self._queues.items():
            if not q:
                continue
            ten = self.tenants[tname]
            if ten.max_slots is None or ten.active_slots < ten.max_slots:
                return True
        return False

    def _loop(self):
        while True:
            with self._cond:
                while not self._closing and not self._runnable_locked():
                    self._cond.wait()
                if self._closing:
                    if not self._drain:
                        self._fail_queued_locked(
                            RuntimeError("server closed"))
                    else:
                        # a drain can only finish requests that CAN run;
                        # a zero-slot tenant's queue would hang it forever
                        for tname, q in self._queues.items():
                            if self.tenants[tname].max_slots == 0:
                                while q:
                                    req = q.popleft()
                                    req.tenant.failed += 1
                                    req.result._finish(
                                        "error", req.t_submit,
                                        exc=RuntimeError(
                                            "server closed while tenant "
                                            f"{tname!r} is slot-capped to "
                                            "0 — request can never run"))
                    if (self._ladder.n_active == 0
                            and not any(self._queues.values())):
                        return
                    if not self._runnable_locked():
                        # closing, undrainable remainder: wait for a
                        # cancel/cap change instead of spinning
                        self._cond.wait(0.05)
                        continue
            try:
                self._iterate()
            except Exception as e:  # noqa: BLE001 — fail in-flight, not the server
                self._fail_inflight(e)

    def _fail_queued_locked(self, exc):
        for q in self._queues.values():
            while q:
                req = q.popleft()
                req.tenant.failed += 1
                req.result._finish("error", req.t_submit, exc=exc)

    def _fail_inflight(self, exc):
        with self._lock:
            for pool in self._ladder.pools.values():
                for s in list(pool.active_slots()):
                    req = pool.owners[s]
                    pool.free(s)
                    req.tenant.active_slots -= 1
                    req.tenant.failed += 1
                    profiler.incr("generation_slot_leave")
                    req.result._finish("error", req.t_submit, exc=exc)
            self._fail_queued_locked(exc)

    def _next_queued_locked(self):
        """Round-robin across tenants with queued work; respects per-
        tenant slot caps.  Returns a request or None."""
        for _ in range(len(self._rr)):
            tname = self._rr.pop(0)
            self._rr.append(tname)
            ten = self.tenants[tname]
            q = self._queues[tname]
            if not q:
                continue
            if (ten.max_slots is not None
                    and ten.active_slots >= ten.max_slots):
                continue
            return q.popleft()
        return None

    def _admit(self):
        """Join queued prefills into free slots (the iteration-level
        half of continuous batching).  In static mode admissions happen
        only into an EMPTY decode batch — the drain-and-refill baseline
        the benchmark compares against."""
        if self.batching == "static" and self._ladder.n_active > 0:
            return
        with profiler.span("generation.admit", "serving"):
            self._admit_queued()

    def _admit_queued(self):
        joined = 0
        while joined < self.max_prefills_per_iter:
            with self._cond:
                req = self._next_queued_locked()
            if req is None:
                return
            if req.result._cancel:
                # cancelled while still queued (client disconnected):
                # finish without ever allocating a slot or prefilling
                with self._lock:
                    req.tenant.cancelled += 1
                profiler.incr("generation_cancelled")
                req.result._finish("cancelled", req.t_submit)
                continue
            if (self._budget is not None
                    and (self._budget_explicit
                         or self._budget.limit_bytes is not None)
                    and not (self._closing and self._drain)
                    and self._budget.under_pressure()):
                # no memory headroom: defer the admission (requeued at
                # the FRONT of its tenant's queue) rather than push the
                # decode loop into RESOURCE_EXHAUSTED.  A draining close
                # is exempt — termination outranks headroom.  The brief
                # wait only happens with NOTHING decoding (it keeps a
                # fully-blocked queue from hot-spinning; while slots are
                # live the decode loop itself paces the scheduler, and a
                # wait here would tax every in-flight request's TPOT).
                profiler.incr("memory_budget_refusal")
                with self._cond:
                    self._queues[req.tenant.name].appendleft(req)
                    self._rr.remove(req.tenant.name)
                    self._rr.insert(0, req.tenant.name)
                    if self._ladder.n_active == 0:
                        self._cond.wait(0.02)
                return
            got = self._ladder.try_alloc(req.max_new, req, req.prompt.size,
                                         self.bos)
            if got is None:
                # no capacity: requeue at the FRONT of its tenant's queue
                # (arrival order within a tenant is preserved)
                with self._cond:
                    self._queues[req.tenant.name].appendleft(req)
                    self._rr.remove(req.tenant.name)
                    self._rr.insert(0, req.tenant.name)
                return
            pool, slot = got
            req.pool, req.slot = pool, slot
            # the slot is claimed: account it to the tenant NOW, before
            # the fallible prefill/insert dispatches — if one raises,
            # _fail_inflight frees the slot and decrements, so the
            # max_slots cap never goes negative
            wait_ms = (_perf() - req.t_submit) * 1e3   # admission - submit
            with self._lock:
                req.tenant.active_slots += 1
                self._queue_waits.append(wait_ms)
            with profiler.span(
                    "generation.prefill", "serving",
                    {"request": req.rid, "tenant": req.tenant.name,
                     "prompt_bucket": req.prompt_bucket, "pool": pool.bucket,
                     "slot": int(slot), "queue_wait_ms": round(wait_ms, 3)}):
                src = _np.zeros((1, req.prompt_bucket), _np.int32)
                src[0, :req.prompt.size] = req.prompt
                mem_k, mem_v = self._prefill_exe.run(
                    f"prefill_{req.prompt_bucket}", params=self.param_arrays,
                    src=src, src_len=_np.int32(req.prompt.size))
                self._exes[pool.bucket].run(
                    "insert", slot=_np.int32(slot), mem_k=mem_k, mem_v=mem_v)
            profiler.incr("generation_prefill")
            profiler.incr("generation_slot_join")
            joined += 1

    def _harvest_cancelled(self):
        for pool in self._ladder.pools.values():
            for s in list(pool.active_slots()):
                req = pool.owners[s]
                if req.result._cancel and not req.result._done:
                    self._leave(pool, s, "cancelled")

    def _leave(self, pool, slot, reason, exc=None):
        req = pool.owners[slot]
        times = req.result._token_times
        with profiler.span(
                "generation.complete", "serving",
                {"request": req.rid, "tenant": req.tenant.name,
                 "reason": reason, "tokens": len(req.result._tokens),
                 "ttft_ms": round((times[0] - req.t_submit) * 1e3, 3)
                 if times else 0.0}):
            self._leave_slot(pool, slot, req, reason, exc)

    def _leave_slot(self, pool, slot, req, reason, exc):
        pool.free(slot)
        profiler.incr("generation_slot_leave")
        with self._lock:
            req.tenant.active_slots -= 1
            if reason == "cancelled":
                req.tenant.cancelled += 1
                profiler.incr("generation_cancelled")
            elif reason == "error":
                req.tenant.failed += 1
            else:
                req.tenant.completed += 1
                self._n_completed += 1
        req.result._finish(reason, req.t_submit, exc=exc)
        if reason in ("eos", "length"):
            self._note_latency(req.result)
            self._judge_slo(req)

    def _judge_slo(self, req):
        res, ten = req.result, req.tenant
        late = ((res.ttft_ms is not None and res.ttft_ms > ten.slo_ttft_ms)
                or (res.tpot_ms is not None
                    and res.tpot_ms > ten.slo_tpot_ms))
        if late:
            profiler.incr("generation_slo_violation")
            with self._lock:
                ten.slo_violations += 1

    def _decode_all(self):
        """One decode iteration: a single compiled step per pool that has
        live slots; emit/finish host-side."""
        for b, pool in self._ladder.pools.items():
            act = pool.active_slots()
            if len(act) == 0:
                continue
            with profiler.span("generation.step", "serving",
                               {"pool": b, "active": int(len(act))}):
                logits = self._exes[b].run(
                    "decode", params=self.param_arrays,
                    tok=pool.last_token.copy(), pos=pool.pos.copy(),
                    mem_len=pool.mem_len.copy())
                with profiler.span("generation.decode.d2h", "serving",
                                   {"pool": b}):
                    logits = _np.asarray(logits)
            now = _perf()
            profiler.incr("generation_decode_iter")
            profiler.incr("generation_token", int(len(act)))
            with profiler.span("generation.decode.emit", "serving",
                               {"pool": b, "active": int(len(act))}):
                self._emit(pool, act, logits, now)
        with self._lock:
            self._iterations += 1

    def _emit(self, pool, act, logits, now):
        """Per-slot argmax, stream push, user callback and leave."""
        emitted = []
        with self._lock:      # ONE acquisition per pool, not per slot
            for s in act:
                req = pool.owners[s]
                nxt = int(logits[s].argmax())
                pool.last_token[s] = nxt
                pool.pos[s] += 1
                req.tenant.tokens += 1
                # under the lock: stats() iterates this window from
                # the metrics-scrape thread
                self._tok_window.append(now)
                emitted.append((s, req, nxt))
        # stream/callback/leave OUTSIDE the lock: on_token is user
        # code and may well call stats() (non-reentrant lock)
        for s, req, nxt in emitted:
            req.result._push(nxt, now)
            if req.on_token is not None:
                try:
                    req.on_token(req.result, nxt)
                except Exception:  # noqa: BLE001 — a bad callback must
                    pass           # not take the decode loop down
            if nxt == self.eos:
                self._leave(pool, s, "eos")
            elif len(req.result._tokens) >= req.max_new:
                self._leave(pool, s, "length")

    def _iterate(self):
        self._harvest_cancelled()
        self._admit()
        self._decode_all()
        # memory-counter-track tick: serving-only processes have no step
        # boundaries, so the scheduler samples the watermark (throttled)
        profiler.maybe_sample_memory()

    # -- observability -------------------------------------------------
    def stats(self):
        pct = profiler.percentile
        with self._lock:
            ttfts, tpots = list(self._ttfts), list(self._tpots)
            waits = list(self._queue_waits)
            queue_depth = sum(len(q) for q in self._queues.values())
            now = _perf()
            recent = [t for t in self._tok_window if now - t <= 10.0]
            out = {
                "queue_depth": queue_depth,
                "active_slots": self._ladder.n_active,
                "total_slots": self._ladder.n_slots,
                "iterations": self._iterations,
                "completed": self._n_completed,
                "tokens_per_s_10s": round(len(recent) / 10.0, 3),
                "ttft_ms_p50": pct(ttfts, 0.50),
                "ttft_ms_p99": pct(ttfts, 0.99),
                "tpot_ms_p50": pct(tpots, 0.50),
                "tpot_ms_p99": pct(tpots, 0.99),
                "queue_wait_ms_p50": pct(waits, 0.50),
                "queue_wait_ms_p95": pct(waits, 0.95),
                "tenants": {t: ten.stats()
                            for t, ten in self.tenants.items()},
            }
        out["pools"] = self._ladder.stats()["buckets"]
        return out

    def _provider(self):
        st = self.stats()
        flat = {k: v for k, v in st.items()
                if isinstance(v, (int, float)) or v is None}
        for tname, ts in st["tenants"].items():
            for k in ("submitted", "shed", "completed", "tokens",
                      "slo_violations", "active_slots"):
                flat[f"tenant_{tname}_{k}"] = ts[k]
        return flat

    def _note_latency(self, res):
        with self._lock:
            if res.ttft_ms is not None:
                self._ttfts.append(res.ttft_ms)
            if res.tpot_ms is not None:
                self._tpots.append(res.tpot_ms)

    def compile_stats(self):
        """Aggregated ``StatefulExecutor.compile_stats()`` across the
        prefill executor and every pool — the harness diffs this around a
        traffic run to prove zero post-warmup compiles."""
        out = {"prefill": self._prefill_exe.compile_stats()}
        for b, exe in self._exes.items():
            out[f"pool_{b}"] = exe.compile_stats()
        out["compiles"] = (out["prefill"]["compiles"]
                          + sum(out[f"pool_{b}"]["compiles"]
                                for b in self._exes))
        return out

    # -- lifecycle -----------------------------------------------------
    def close(self, drain=True, timeout=60.0):
        """Stop accepting requests.  ``drain=True`` (default) finishes
        everything queued and in flight under a ``timeout`` deadline —
        whatever the drain could not finish in time fails with a
        retriable :class:`ServerDrainingError` instead of hanging its
        clients; ``drain=False`` fails queued requests immediately and
        cancels in-flight ones at the next boundary."""
        with self._cond:
            if self._closed:
                return
            self._drain = bool(drain)
            self._closing = True
            if not drain:
                for q in self._queues.values():
                    for req in q:
                        req.tenant.failed += 1
                        req.result._finish(
                            "error", req.t_submit,
                            exc=ServerDrainingError(
                                "server closed without drain — retry "
                                "against another replica"))
                    q.clear()
                for pool in self._ladder.pools.values():
                    for s in pool.active_slots():
                        pool.owners[s].result._cancel = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():
                # drain deadline exceeded: fail what's still queued
                # retriably and cancel the in-flight remainder so no
                # client blocks on a server that will never answer
                with self._cond:
                    for q in self._queues.values():
                        for req in q:
                            req.tenant.failed += 1
                            req.result._finish(
                                "error", req.t_submit,
                                exc=ServerDrainingError(
                                    f"drain deadline ({timeout}s) "
                                    "exceeded — retry against another "
                                    "replica"))
                        q.clear()
                    for pool in self._ladder.pools.values():
                        for s in pool.active_slots():
                            pool.owners[s].result._cancel = True
                    self._cond.notify_all()
        profiler.unregister_metrics_provider(self.name)
        self._ladder.release()   # pool bytes leave the device-memory ledger
        with self._cond:
            self._closed = True
            # _closing stays latched: there is no reopen (start() raises
            # once closed), and clearing it would let a scheduler thread
            # that outlived the join timeout spin forever on its queues

    def __enter__(self):
        return self.start()

    def __exit__(self, *a):
        self.close()
        return False
