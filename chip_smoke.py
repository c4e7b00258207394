#!/usr/bin/env python
"""chip_smoke.py — does the system still start on the chip?

ONE process drives the repo's main paths once, through the entry points a
user would call, at the full width of models the repo supports, and checks
what comes out.  Three phases in sequence; any failure in any phase is a
non-zero exit with its traceback (nothing here turns a failure into a
warning):

* **kernels** — the Pallas flash-attention kernels, compiled by Mosaic at
  the lengths the dispatcher itself hands them (forward at S=1024 and
  4096, forward+backward at S=8192; bf16, D=64, causal and not), against
  ``attention_reference``; the lowering of every call must contain the
  Mosaic custom call, so a quiet route to the XLA reference cannot pass.
* **train** — BERT-base pretraining exactly as ``bench.py`` builds it
  (B=64, S=128, P=20, bf16 AMP, fp32 Adam masters) through
  ``make_mesh()`` → ``SPMDTrainer`` → ``shard_batch`` → ``step`` on ALL the
  chips JAX holds (pure dp); on a four-chip host also ``fsdp=2 × tp=2``
  with ``bert_sharding_rules(fsdp=True)``.
* **serve** — Transformer-big (vocab 32768) behind a ``GenerationServer``
  under ``compile_guard="raise"``: mixed-length prompts submitted
  concurrently, every ``result()`` read, and one prompt decoded alone must
  equal the same prompt decoded amid the others, token for token.

Needs a TPU: with none it exits non-zero naming the platform it found and
prints no result.  ``--dry-run-cpu`` is the explicit CPU rehearsal — JAX
pinned to the CPU, tiny widths, the kernels in the Pallas interpreter
(asked for by name), output labelled ``"dry_run": true``, no device
assertions and no time printed.  Compile seconds and step milliseconds are
printed as set-up facts of this run, not as benchmark metrics.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""
import argparse
import collections
import gc
import json
import math
import os
import sys
import time

import numpy as np

# full width on the chip / tiny for the CPU rehearsal
FULL = {
    "kernels": {"bh": 2, "d": 64, "fwd": (1024, 4096), "fwd_bwd": (8192,)},
    "train": {"B": 64, "S": 128, "P": 20, "vocab": 30522, "bert_kwargs": None,
              "steps": 10},
    "serve": {"vocab": 32768, "model_kwargs": None, "max_prompt": 64,
              "max_new": 32, "slots": 4, "n_prompts": 8},
}
TINY = {
    "kernels": {"bh": 2, "d": 64, "fwd": (256,), "fwd_bwd": (256,)},
    "train": {"B": 8, "S": 16, "P": 4, "vocab": 512, "steps": 6,
              "bert_kwargs": dict(units=64, hidden_size=128, num_layers=2,
                                  num_heads=2, max_length=32)},
    "serve": {"vocab": 128, "max_prompt": 16, "max_new": 8, "slots": 4,
              "n_prompts": 8,
              "model_kwargs": dict(units=32, hidden_size=64, num_heads=2,
                                   num_encoder_layers=1, num_decoder_layers=1,
                                   dropout=0.0, max_length=64)},
}


def say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, what):
    """An assertion that survives ``python -O``."""
    if not cond:
        raise AssertionError(what)


def per_device_bytes(arrays):
    """{device: bytes of ``arrays``' shards resident on it}."""
    import jax

    out = collections.Counter()
    for a in jax.tree_util.tree_leaves(arrays):
        for sh in a.addressable_shards:
            out[sh.device] += sh.data.nbytes
    return out


def check_on_devices(arrays, devices, what):
    import jax

    allowed = set(devices)
    for a in jax.tree_util.tree_leaves(arrays):
        check(set(a.devices()) <= allowed,
              f"{what}: array on {a.devices()}, expected only {devices}")


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------


def phase_kernels(cfg, dry_run):
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops.attention import (
        attention_reference, flash_attention)

    rng = np.random.RandomState(0)
    bh, d = cfg["bh"], cfg["d"]

    def qkv(s):
        return [jnp.asarray(rng.randn(1, bh, s, d).astype(np.float32) * 0.5,
                            jnp.bfloat16) for _ in range(3)]

    def close(got, want, what):
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        check(np.isfinite(got).all(), f"{what}: non-finite values")
        # bf16 in and out: 2^-8 relative to the largest reference value
        tol = 2e-2 * max(1.0, float(np.abs(want).max()))
        err = float(np.abs(got - want).max())
        check(err <= tol, f"{what}: max |err| {err:.4g} > {tol:.4g}")
        return err

    def check_lowering(fn, args, n_calls, what):
        if dry_run:
            return  # the interpreter emits no Mosaic custom call
        n = fn.lower(*args).as_text().count("tpu_custom_call")
        check(n == n_calls, f"{what}: {n} Mosaic custom calls in the "
                            f"lowering, expected {n_calls}")

    for causal in (False, True):
        for s in cfg["fwd"]:
            q, k, v = qkv(s)
            fwd = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=causal))
            ref = jax.jit(lambda q, k, v: attention_reference(q, k, v, causal=causal))
            what = f"flash fwd S={s} causal={causal}"
            check_lowering(fwd, (q, k, v), 1, what)
            err = close(fwd(q, k, v), ref(q, k, v), what)
            say(f"kernels: {what} ok (max err {err:.3g})")
        for s in cfg["fwd_bwd"]:
            q, k, v = qkv(s)

            def loss(attn):
                return lambda q, k, v: attn(q, k, v, causal=causal).astype(
                    jnp.float32).sum()

            grad = jax.jit(jax.grad(loss(flash_attention), argnums=(0, 1, 2)))
            gref = jax.jit(jax.grad(loss(attention_reference), argnums=(0, 1, 2)))
            what = f"flash fwd+bwd S={s} causal={causal}"
            # forward-with-lse, the one-pass backward
            check_lowering(grad, (q, k, v), 2, what)
            errs = [close(g, r, f"{what} d{n}")
                    for g, r, n in zip(grad(q, k, v), gref(q, k, v), "qkv")]
            say(f"kernels: {what} ok (max err dq/dk/dv "
                f"{'/'.join(f'{e:.3g}' for e in errs)})")


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------


def train_layout(cfg, dry_run, label, mesh_kwargs, rules):
    """One SPMDTrainer run of the BERT workload under one mesh layout."""
    import jax

    from bench import _fence, build_bert_pretrain
    from incubator_mxnet_tpu import amp, profiler
    from incubator_mxnet_tpu.parallel import SPMDTrainer, make_mesh

    devices = jax.devices()
    try:
        net, (tok, seg, pos), labels, mlm_loss, mp = build_bert_pretrain(
            cfg["B"], S=cfg["S"], P=cfg["P"], vocab=cfg["vocab"],
            bert_kwargs=cfg["bert_kwargs"])
        mesh = make_mesh(**mesh_kwargs)
        check(mesh.devices.size == len(devices),
              f"train[{label}]: mesh uses {mesh.devices.size} of "
              f"{len(devices)} devices")
        trainer = SPMDTrainer(
            net, mlm_loss, "adam",
            {"learning_rate": 1e-4, "multi_precision": mp},
            mesh=mesh, rules=rules)
        tok, seg, pos, labels = trainer.shard_batch(tok, seg, pos, labels)
        inputs = (tok, seg, pos)

        t0 = time.perf_counter()
        losses = [trainer.step(inputs, labels)]
        first = float(np.asarray(losses[0]._data))  # D2H: compile + step 0
        compile_s = time.perf_counter() - t0
        losses.append(trainer.step(inputs, labels))  # warm-up, guard armed
        np.asarray(losses[-1]._data)
        recompiles_before = profiler.counters()["recompile_steady_state"]
        n_timed = cfg["steps"] - 2
        t0 = time.perf_counter()
        for _ in range(n_timed):
            losses.append(trainer.step(inputs, labels))
        _fence(trainer, losses[-1])                  # D2H ends the window
        step_ms = (time.perf_counter() - t0) / n_timed * 1e3
        recompiles = (profiler.counters()["recompile_steady_state"]
                      - recompiles_before)

        values = [float(np.asarray(l._data)) for l in losses]
        last = values[-1]
        check(all(math.isfinite(v) for v in values),
              f"train[{label}]: non-finite loss in {values}")
        # an untrained model is near-uniform over the vocabulary; BERT-base's
        # init puts ~0.6 of logit variance on top of ln(V) (10.94 on the
        # chip and on the CPU in fp32 alike), so the band is 1.0
        uniform = math.log(cfg["vocab"])
        check(abs(first - uniform) <= 1.0,
              f"train[{label}]: step-0 loss {first:.3f} not within 1.0 of "
              f"ln(vocab) = {uniform:.3f}")
        check(last < first, f"train[{label}]: loss did not fall "
                            f"({first:.4f} -> {last:.4f})")
        check(recompiles == 0,
              f"train[{label}]: {recompiles} recompiles after warm-up")
        state = (trainer._param_arrays, trainer._opt_states)
        check_on_devices(state, devices, f"train[{label}] params/opt state")
        param_bytes = per_device_bytes(trainer._param_arrays)
        check(set(param_bytes) == set(devices),
              f"train[{label}]: parameters live on {sorted(map(str, param_bytes))}"
              f", not on all of {devices}")
        peaks = {}
        if not dry_run:  # CPU devices report no memory_stats
            for dev in devices:
                peaks[str(dev)] = dev.memory_stats()["peak_bytes_in_use"]
                check(peaks[str(dev)] > param_bytes[dev],
                      f"train[{label}]: {dev} peak {peaks[str(dev)]} B does "
                      f"not exceed its {param_bytes[dev]} B of parameters")
        out = {"layout": label, "mesh": {k: v for k, v in mesh.shape.items()
                                         if v > 1},
               "loss_first": round(first, 4), "loss_last": round(last, 4),
               "recompiles_after_warmup": recompiles,
               "param_bytes_per_device": sorted(param_bytes.values()),
               "peak_bytes_per_device": sorted(peaks.values())}
        if not dry_run:
            out["compile_s"] = round(compile_s, 1)
            out["steady_step_ms"] = round(step_ms, 2)
        say(f"train[{label}]: {json.dumps(out)}")
        return out
    finally:
        # process-global state the next phase must not inherit
        amp.disable()
        profiler.disarm_compile_guard()


def phase_train(cfg, dry_run):
    import jax

    from incubator_mxnet_tpu.gluon.model_zoo.bert import bert_sharding_rules

    n = len(jax.devices())
    replicated = train_layout(cfg, dry_run, f"dp{n}", {}, None)
    if n % 4 == 0:
        # the __graft_entry__ layout at real width: ZeRO-sharded state over
        # fsdp, Megatron TP on QKV/FFN — every chip holds shards only
        gc.collect()
        sharded = train_layout(cfg, dry_run, f"dp{n // 4}xfsdp2xtp2",
                               {"fsdp": 2, "tp": 2},
                               bert_sharding_rules(fsdp=True))
        check(max(sharded["param_bytes_per_device"])
              < min(replicated["param_bytes_per_device"]),
              f"train: sharded layout holds "
              f"{sharded['param_bytes_per_device']} B/chip, not below the "
              f"replicated {replicated['param_bytes_per_device']}")
    gc.collect()


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------


def phase_serve(cfg, dry_run):
    import jax

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import profiler
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import (
        Transformer, transformer_big)
    from incubator_mxnet_tpu.serving import GenerationServer

    vocab, bos, eos = cfg["vocab"], 1, 2
    mx.random.seed(0)
    if cfg["model_kwargs"] is None:
        net = transformer_big(vocab_size=vocab, max_length=512)
    else:
        net = Transformer(vocab, **cfg["model_kwargs"])
    # Normal(0.3), not the default init: with tied embeddings the default
    # decodes one repeated token whatever the prompt, and the equivalence
    # check below would pass on anything
    net.initialize(mx.init.Normal(0.3))
    ones = lambda n: mx.nd.array(np.ones((1, n), np.int32), dtype="int32")
    net(ones(8), ones(1))  # materialise deferred shapes

    rng = np.random.RandomState(0)
    lengths = rng.randint(2, cfg["max_prompt"] + 1, cfg["n_prompts"])
    lengths[0], lengths[-1] = cfg["max_prompt"], 2  # both ends of the ladder
    prompts = [rng.randint(3, vocab, int(n)).astype(np.int32) for n in lengths]

    profiler.set_config(compile_guard="raise")
    recompiles_before = profiler.counters()["recompile_steady_state"]
    t0 = time.perf_counter()
    srv = GenerationServer(net, bos=bos, eos=eos,
                           max_prompt_length=cfg["max_prompt"],
                           max_new_tokens=cfg["max_new"],
                           slots_per_bucket=cfg["slots"])
    start_s = time.perf_counter() - t0
    try:
        check_on_devices(srv.param_arrays, jax.devices()[:1],
                         "serve: server weights")
        # alone first, then amid the others: must match token for token
        alone = srv.submit(prompts[3])
        alone_tokens = alone.result(timeout=300)
        t0 = time.perf_counter()
        handles = [srv.submit(p) for p in prompts]
        # result() re-raises what the scheduler thread caught: a compile
        # error or OOM there fails only its requests and leaves rc 0
        tokens = [h.result(timeout=300) for h in handles]
        wall_s = time.perf_counter() - t0
        for h, toks in zip([alone] + handles, [alone_tokens] + tokens):
            check(h.finish_reason in ("eos", "length"),
                  f"serve: request {h.request_id} ended {h.finish_reason!r}")
            check(1 <= len(toks) <= cfg["max_new"]
                  and ((toks >= 0) & (toks < vocab)).all(),
                  f"serve: request {h.request_id} returned {toks}")
        check(np.array_equal(alone_tokens, tokens[3]),
              f"serve: prompt decoded alone {alone_tokens.tolist()} != "
              f"amid others {tokens[3].tolist()}")
        check(len({t.tobytes() for t in tokens}) > 1,
              "serve: every prompt decoded to the same tokens")
        recompiles = (profiler.counters()["recompile_steady_state"]
                      - recompiles_before)
        check(recompiles == 0, f"serve: {recompiles} compiles after warm-up")
        n_tokens = int(sum(len(t) for t in tokens))
        out = {"requests": len(handles) + 1, "tokens": n_tokens,
               "finish": sorted({h.finish_reason for h in handles}),
               "alone_equals_amid": True,
               "recompiles_after_warmup": recompiles}
        if not dry_run:
            out["server_start_s"] = round(start_s, 1)
            tpots = [h.tpot_ms for h in handles if h.tpot_ms is not None]
            out["decode_ms_per_token_median"] = round(float(np.median(tpots)), 2)
            out["batch_wall_s"] = round(wall_s, 2)
            out["peak_bytes"] = jax.devices()[0].memory_stats()[
                "peak_bytes_in_use"]
        say(f"serve: {json.dumps(out)}")
    finally:
        srv.close()
        profiler.set_config(compile_guard=None)
        profiler.disarm_compile_guard()


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="explicit CPU rehearsal: tiny widths, interpreted "
                         "kernels, no device assertions, no times")
    args = ap.parse_args(argv)
    dry_run = args.dry_run_cpu
    t_start = time.perf_counter()

    if dry_run:
        # asked for by name: the flash kernels, forward and backward, run
        # in the Pallas interpreter whatever the length
        os.environ["MXNET_TPU_FLASH"] = "interpret"
    import jax
    import jaxlib

    if dry_run:
        jax.config.update("jax_platforms", "cpu")
    # before anything is printed: in a directory that holds this script and
    # nothing else of the repo, this import is the failure
    import incubator_mxnet_tpu as mx

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu" and not dry_run:
        sys.exit(f"chip_smoke: needs a TPU, but jax.devices()[0].platform is "
                 f"{device['platform']!r} ({devices}); --dry-run-cpu "
                 f"rehearses the path on the CPU")
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    say(f"device {json.dumps(device)}  jax {jax.__version__} "
        f"jaxlib {jaxlib.__version__} libtpu {libtpu_version} "
        f"python {sys.version.split()[0]}")

    cache_dir = mx.config.enable_compile_cache()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    say(f"compile cache {cache_dir} ({entries} entries at start — "
        f"{'warm' if entries else 'cold'})")

    cfg = TINY if dry_run else FULL
    for name, phase in (("kernels", phase_kernels), ("train", phase_train),
                        ("serve", phase_serve)):
        t0 = time.perf_counter()
        phase(cfg[name], dry_run)
        say(f"phase {name} passed"
            + ("" if dry_run else f" in {time.perf_counter() - t0:.1f} s"))
    if not dry_run:
        say(f"total {time.perf_counter() - t_start:.1f} s "
            f"(compile cache {'warm' if entries else 'cold'})")
    result = {"ok": True, "device": device}
    if dry_run:
        result["dry_run"] = True
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
