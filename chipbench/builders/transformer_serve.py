"""Build the encoder-decoder Transformer behind ``GenerationServer``.

Re-states ``chip_smoke.py``'s serve phase: ``Normal(sigma)`` weights (with tied
embeddings the default init decodes one repeated token whatever the prompt,
and every equality check below would pass on anything; the configuration
file says why its sigma), one forward to materialise shapes,
``compile_guard="raise"`` armed before the server is built.  The configuration passes the server its deployment shape only (ladder
tops, slots, queue capacity); every scheduling choice stays at the program's
default.
"""
from __future__ import annotations

import functools
import time

import numpy as np

from .. import trace_read
from ..reference import transformer as reference

# Which positions of a probe give a verdict.  Server and reference both compute
# in float32 at "highest" matmul precision and differ by rounding order only,
# but random weights make some positions ill-conditioned: a near-tie in one
# softmax amplifies a rounding error into a different token (PERF.md, PR 24).
# So the reference is run again with its embedding perturbed by one float32
# rounding (relative 1e-6), twice, and a position counts only where the
# reference's top-two margin exceeds SENSITIVITY_FACTOR times the largest logit
# change those perturbations caused, and MARGIN_TOLERANCE_STD_SHARE of the
# logits' standard deviation.  A server computing in bf16 is a perturbation
# some 4000 times larger and flips every position whose margin is less than
# that many times its sensitivity, which is most of them.
MARGIN_TOLERANCE_STD_SHARE = 1e-3
SENSITIVITY_FACTOR = 10.0
PERTURBATION = 1e-6
PROBE_LENGTHS = (5, 23, 47, 120, 5, 23, 47, 120)  # four rungs of the prompt ladder, twice
PROBE_NEW_TOKENS = 12
BOS, EOS, FIRST_TOKEN = 1, 2, 3


def build(ctx):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import profiler
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import Transformer
    from incubator_mxnet_tpu.serving import GenerationServer

    c = ctx.config
    t0 = time.perf_counter()
    mx.random.seed(ctx.seed31)
    net = Transformer(
        c["vocab_size"], units=c["d_model"], hidden_size=c["d_ff"],
        num_heads=c["num_heads"], num_encoder_layers=c["encoder_layers"],
        num_decoder_layers=c["decoder_layers"], dropout=c["dropout"],
        max_length=c["max_position_embeddings"])
    net.initialize(mx.init.Normal(c["init_normal_sigma"]))
    ones = lambda n: mx.nd.array(np.ones((1, n), np.int32), dtype="int32")
    net(ones(8), ones(1))  # materialise deferred shapes
    ctx.say(f"model initialised in {time.perf_counter() - t0:.1f} s")

    profiler.set_config(compile_guard="raise")
    recompiles_before = profiler.counters()["recompile_steady_state"]
    shape = c["server"]
    t0 = time.perf_counter()
    server = GenerationServer(
        net, bos=BOS, eos=EOS,
        max_prompt_length=shape["max_prompt_length"],
        max_new_tokens=shape["max_new_tokens"],
        slots_per_bucket=shape["slots_per_bucket"],
        tenants={"default": {"max_queue": shape["max_queue"]}})
    stats = server.stats()
    ctx.say(f"server up in {time.perf_counter() - t0:.1f} s: pools "
            f"{ {b: p['nbytes'] for b, p in stats['pools'].items()} } bytes, "
            f"{stats['total_slots']} slots")
    ctx.say_memory("with the server up and warm")

    system = {"server": server, "vocab": c["vocab_size"], "eos": EOS,
              "first_token": FIRST_TOKEN, "config": c,
              "recompiles": lambda: (profiler.counters()["recompile_steady_state"]
                                     - recompiles_before),
              "close": functools.partial(_close, server)}
    try:
        probes = _decode_probes(ctx, server)
        system["checks"] = {
            "outputs_differ": len({p["tokens"].tobytes() for p in probes}) > 1,
            "program_latency_fields_agree": max(p["clock_diff_ms"] for p in probes) < 2.0}
        system["alone"] = probes[3]
        # the reference runs AFTER the window and after the memory reading:
        # its weights' copies and scratch are the benchmark's, not the system's
        system["late_checks"] = functools.partial(
            _reference_check, ctx, net, server, probes)
        system["programs"] = _identify_programs(ctx, server) if ctx.trace else {}
    except BaseException:
        _close(server)
        raise
    return system


def _close(server):
    from incubator_mxnet_tpu import profiler

    server.close(drain=False, timeout=30.0)
    profiler.set_config(compile_guard=None)
    profiler.disarm_compile_guard()


def _probe_prompts(ctx):
    c = ctx.config
    rng = np.random.RandomState(ctx.seed31)
    top = c["server"]["max_prompt_length"]
    return [rng.randint(FIRST_TOKEN, c["vocab_size"], min(n, top)).astype(np.int32)
            for n in PROBE_LENGTHS]


def _decode_probes(ctx, server):
    """Seeded probes through the idle server, one at a time; the program's
    TTFT/TPOT fields against the benchmark's own clock."""
    t0 = time.perf_counter()
    new = min(PROBE_NEW_TOKENS, ctx.config["server"]["max_new_tokens"])
    probes = []
    for prompt in _probe_prompts(ctx):
        times = []
        t_submit = time.perf_counter()
        handle = server.submit(prompt, max_new_tokens=new,
                               on_token=lambda r, tok: times.append(time.perf_counter()))
        tokens = handle.result(timeout=300.0)
        if handle.finish_reason not in ("eos", "length"):
            raise RuntimeError(f"probe ended {handle.finish_reason!r}")
        diff = abs((times[0] - t_submit) * 1e3 - handle.ttft_ms)
        if len(times) > 1:
            own = (times[-1] - times[0]) / (len(times) - 1) * 1e3
            diff = max(diff, abs(own - handle.tpot_ms))
        probes.append({"prompt": prompt, "max_new": new, "tokens": tokens,
                       "clock_diff_ms": diff})
    ctx.say(f"{len(probes)} probes decoded alone in {time.perf_counter() - t0:.1f} s; "
            f"the program's ttft/tpot fields differ from the benchmark's clock "
            f"by at most {max(p['clock_diff_ms'] for p in probes):.3f} ms; "
            f"first probe decoded {probes[0]['tokens'].tolist()}")
    return probes


def _reference_check(ctx, net, server, probes):
    """The probes' tokens against the reference's argmax, teacher-forced on
    the server's own tokens, wherever the reference's margin allows a verdict."""
    import jax

    c = ctx.config
    t0 = time.perf_counter()
    names = [p.name for p in sorted(net.collect_params().values(),
                                    key=lambda p: p.name)]
    params = dict(zip(names, server.param_arrays))
    ref_fn = jax.jit(functools.partial(
        reference.logits, enc_layers=c["encoder_layers"],
        dec_layers=c["decoder_layers"], heads=c["num_heads"]))
    embed = next(n for n in names if n.endswith("embed_weight"))
    nudge = jax.jit(lambda w, key: w * (1 + PERTURBATION * jax.random.normal(
        key, w.shape, w.dtype)))
    nudged = [dict(params, **{embed: nudge(params[embed], jax.random.PRNGKey(k))})
              for k in (1, 2)]
    compared = equal = positions = 0
    for probe in probes:
        prompt, tokens, new = probe["prompt"], probe["tokens"], probe["max_new"]
        tgt = np.concatenate([[BOS], tokens[:-1]]).astype(np.int32)
        # pad the target to the fixed probe length so that the reference
        # compiles once per prompt length, not once per output length
        pad = np.full(new - len(tgt), EOS, np.int32)
        tgt = np.concatenate([tgt, pad])
        logits = np.asarray(ref_fn(params, prompt, tgt))[:len(tokens)]
        moved = np.max([np.abs(np.asarray(ref_fn(p, prompt, tgt))[:len(tokens)]
                               - logits).max(-1) for p in nudged], axis=0)
        top2 = np.sort(logits, axis=-1)[:, -2:]
        margin = top2[:, 1] - top2[:, 0]
        sure = (margin > MARGIN_TOLERANCE_STD_SHARE * logits.std()) \
            & (margin > SENSITIVITY_FACTOR * moved)
        positions += len(tokens)
        compared += int(sure.sum())
        equal += int((logits.argmax(-1)[sure] == tokens[sure]).sum())
    ctx.say(f"reference check in {time.perf_counter() - t0:.1f} s, after the "
            f"window: {compared} of {positions} positions give a verdict, "
            f"{equal} of them equal the reference's argmax")
    return {"tokens_match_reference": equal == compared and 2 * compared >= positions}


def _identify_programs(ctx, server):
    """Which ``jit_pure(<fingerprint>)`` is which program.  Every server
    program traces under the same function name, so run one request per rung
    of the ladders, alone, under a trace of its own BEFORE the measured
    window: the device then executes prefill, insert and one decode per token,
    in that order, and the fingerprints can be read off by position."""
    from incubator_mxnet_tpu.serving import ShapeBucketer

    c = ctx.config["server"]
    rng = np.random.RandomState(0)
    prompt_buckets = list(ShapeBucketer(max_length=c["max_prompt_length"]).buckets)
    decode_buckets = sorted(int(b) for b in server.stats()["pools"])
    rungs = max(len(prompt_buckets), len(decode_buckets))
    plan = []
    for k in range(rungs):
        pb = prompt_buckets[min(k, len(prompt_buckets) - 1)]
        j = min(k, len(decode_buckets) - 1)
        # the smallest budget that lands in pool j: one more than pool j-1 holds
        budget = decode_buckets[j - 1] + 1 if j else min(4, decode_buckets[0])
        plan.append((pb, decode_buckets[j], budget))
    t0 = time.perf_counter()
    with ctx.traced_span("identify"):
        counts = []
        for pb, _, budget in plan:
            prompt = rng.randint(FIRST_TOKEN, ctx.config["vocab_size"], pb)
            counts.append(len(server.submit(
                prompt.astype(np.int32), max_new_tokens=budget).result(timeout=300.0)))
    trace = trace_read.load(ctx.trace_path("identify"))
    if not trace.devices:
        ctx.say("the identification trace has no TPU plane; per-program "
                "metrics are left out")
        return {}
    modules = [n for _, _, n in sorted(trace.devices[0]["modules"])
               if n.startswith("jit_pure(")]
    labels, i = {}, 0
    for (pb, pool, _), n in zip(plan, counts):
        # the insert program passes the pool's own caches through untouched,
        # so pools may share one compiled insert: it carries no pool's name
        for label in [f"prefill_{pb}", "insert"] + [f"decode_{pool}"] * n:
            seen = labels.setdefault(modules[i], label) if i < len(modules) else None
            if seen != label:
                ctx.say(f"program identification failed at execution {i} of "
                        f"{len(modules)}: expected {label}, found {seen}; "
                        f"per-program metrics are left out")
                return {}
            i += 1
    ctx.say(f"identified {len(labels)} programs in {time.perf_counter() - t0:.1f} s: "
            f"{ {v: k for k, v in labels.items()} }")
    return labels
