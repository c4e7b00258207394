"""Run training steps back to back, each on a FRESH batch that the program's
input path reads from a record file.

Set-up writes ``records`` pre-masked BERT instances from ``--seed`` (drawn by
``traffic_gen.train_batch``, as the resident cells' one batch is) into ONE
indexed RecordIO file in a temporary directory that the run removes when it
ends, and reads the file back once with plain numpy: the reference for data.
The steps then read it as a user would, ``gluon.data.RecordFileDataset`` ->
``gluon.data.DataLoader`` (shuffled from ``--seed``, the short last batch
dropped) -> ``io.DataPipeline`` on the trainer's mesh -> ``trainer.step``.
The pipeline is handed ONE endless stream, a generator that iterates the
loader again, with a new permutation, whenever it ends (the source's
``d.repeat()``): ``DataPipeline.reset()`` joins and restarts every stage, and
with epochs of seconds where a user's last hours it would weigh a thousand
times what it weighs in a deployment.  No depth, worker count or budget is
set: the pipeline's own bounded prefetch holds the host back.

The builders return closures only (``"step": lambda: trainer.step((tok, seg,
pos), labels)``), so the trainer and the resident batch - for shapes, dtypes
and shardings - are taken from the closure, loudly.  Window, warm-up and
fence are ``train_steps``'; ``chipbench.train_step`` is round ``trainer.step``
alone, so ``dispatch_ms.train`` means what it means in the other cells, and
``chipbench.next_batch`` is round ``next(pipeline)``.

This driver reads the program's counter ``io_pipeline_wait_us``, which came
with the input path's spans (PR 36): a program without it cannot run a cell
of this driver, and says so as soon as the module is imported.
"""
from __future__ import annotations

import contextlib
import inspect
import math
import os
import tempfile
import time

import numpy as np

from incubator_mxnet_tpu import profiler

from .. import traffic_gen

_perf = time.perf_counter

FIELDS = ("tok", "seg", "pos", "labels")  # a record: four int32 arrays
RECORDIO_MAGIC = 0xCED7230A
LOSS_MEAN_OVER = 8

if "io_pipeline_wait_us" not in profiler.counters():
    raise ImportError(
        "chipbench.drivers.train_feed: this program's io.DataPipeline has no "
        "io_pipeline_wait_us counter (it is older than its input-path spans), "
        "so the cells of this driver cannot run on it")


def run(system, ctx):
    import jax

    from incubator_mxnet_tpu.gluon.data import DataLoader, RecordFileDataset
    from incubator_mxnet_tpu.io import DataPipeline

    trainer, resident = trainer_and_batch(system["step"])
    fence = system["fence"]
    t = ctx.traffic
    warmup = int(t["warmup_steps"])
    batch = int(resident[0].shape[0])
    widths = [int(np.prod(a.shape[1:])) for a in resident]
    with contextlib.ExitStack() as stack:
        t0 = _perf()
        folder = stack.enter_context(
            tempfile.TemporaryDirectory(prefix="chipbench-feed-"))
        path = os.path.join(folder, "instances.rec")
        table = write_instances(path, t, ctx.seed, ctx.config["vocab_size"])
        dataset = RecordFileDataset(path)
        stack.callback(dataset._record.close)
        loader = DataLoader(dataset.transform(decoder(widths)), batch_size=batch,
                            sampler=SeededShuffle(len(dataset), ctx.seed),
                            last_batch="discard")
        per_epoch = len(loader)

        def repeat():
            while True:
                yield from loader

        feed = DataPipeline(repeat, mesh=trainer.mesh)
        stack.callback(feed.close)
        ctx.say(f"{len(dataset)} records of {4 * sum(widths)} B written to "
                f"{os.path.basename(path)} ({os.path.getsize(path)} B) and the "
                f"pipeline started in {_perf() - t0:.2f} s: {per_epoch} batches "
                f"of {batch} an epoch")

        def step():
            *data, label = next(feed)
            return trainer.step(tuple(data), label)

        t0 = _perf()
        first_loss = fence(step())
        ctx.say(f"step 0: loss {first_loss:.4f}, {_perf() - t0:.1f} s with its "
                f"compile or cache load")
        t0 = _perf()
        for _ in range(warmup):
            loss = step()
        fence(loss)
        ctx.say(f"warm-up: {(_perf() - t0) / warmup * 1e3:.2f} ms a step "
                f"(fenced, {warmup} steps)")

        before = profiler.counters()
        dispatch_s, delivered, losses = [], [], []
        with ctx.window() as window:
            end = window.t0 + ctx.window_seconds
            while _perf() < end:
                with jax.profiler.TraceAnnotation("chipbench.next_batch"):
                    *data, label = got = next(feed)
                t1 = _perf()
                with jax.profiler.TraceAnnotation("chipbench.train_step"):
                    loss = trainer.step(tuple(data), label)
                dispatch_s.append(_perf() - t1)
                delivered.append(got)
                losses.append(loss)
            with jax.profiler.TraceAnnotation("chipbench.fence"):
                last_loss = fence(loss)
        after = profiler.counters()
        moved = {k: after[k] - before[k] for k in (
            "recompile_steady_state", "io_pipeline_batches",
            "io_pipeline_stalls", "io_pipeline_wait_us")}

        steps = len(dispatch_s)
        elapsed = window.t1 - window.t0
        chips = len(ctx.devices)
        rate = steps * system["tokens_per_step"] / elapsed / chips
        k = max(1, min(LOSS_MEAN_OVER, steps // 2))
        read = lambda some: [float(np.asarray(x._data)) for x in some]
        head, tail = read(losses[:k]), read(losses[-k:])
        first = 1 + warmup  # batches the stream had given before the window
        epochs = (first + steps - 1) // per_epoch - first // per_epoch
        # NDArray leaves from the loader's default batchify, bare jax.Arrays
        # from one that stays numpy
        delivered = [[getattr(a, "_data", a) for a in got] for got in delivered]
        sharded_alike = all(a.sharding == r.sharding
                            for got in delivered for a, r in zip(got, resident))
        t0 = _perf()
        whole = feed_matches_file([[np.asarray(a) for a in got] for got in delivered],
                                  table, widths[0], first, per_epoch)
        ctx.say(f"window: {steps} steps in {elapsed:.3f} s = "
                f"{elapsed / steps * 1e3:.3f} ms a step, {rate:.1f} tokens/s/chip; "
                f"loss {first_loss:.4f} -> {last_loss:.4f}, the first {k} window "
                f"steps' mean {np.mean(head):.4f} (std {np.std(head):.4f}), the "
                f"last {k}'s {np.mean(tail):.4f} (std {np.std(tail):.4f}); "
                f"recompiles {moved['recompile_steady_state']}")
        ctx.say(f"input: {moved['io_pipeline_batches']} batches delivered over "
                f"{epochs} epoch boundaries, {moved['io_pipeline_stalls']} stalls, "
                f"io_pipeline_wait_us {moved['io_pipeline_wait_us']} "
                f"({moved['io_pipeline_wait_us'] / steps:.1f} us a step); "
                f"pipeline {feed.stats()}; {steps * batch} delivered rows "
                f"looked up in the file in {_perf() - t0:.2f} s")
    checks = dict(system["checks"])
    checks.update({
        "loss_finite": all(map(math.isfinite, [first_loss, last_loss] + head + tail)),
        "loss_fell": bool(np.mean(tail) < np.mean(head)),
        "no_compile_in_window": moved["recompile_steady_state"] == 0,
        "a_fresh_batch_every_step": (moved["io_pipeline_batches"] == steps
                                     and sharded_alike),
        "feed_matches_file": whole})
    return {"values": {"train_tokens_per_s_chip": rate},
            "counts": {"dispatch_ms_median": float(np.median(dispatch_s)) * 1e3,
                       "steps": steps, "batches": moved["io_pipeline_batches"],
                       "epochs": epochs,
                       "input_wait_us": moved["io_pipeline_wait_us"]},
            "shapes": system["shapes"],
            "checks": checks, "attempted": steps, "failed": 0}


def trainer_and_batch(step):
    """The trainer and the resident batch (``FIELDS``' order) out of the
    builder's ``lambda: trainer.step((tok, seg, pos), labels)``."""
    held = inspect.getclosurevars(step).nonlocals
    missing = [name for name in ("trainer",) + FIELDS if name not in held]
    if missing:
        raise RuntimeError(
            f"train_feed takes the trainer and the resident batch from the "
            f"closure of the builder's step: it holds {sorted(held)} and not "
            f"{missing}")
    return held["trainer"], [held[name] for name in FIELDS]


def write_instances(path, traffic, seed, vocab):
    """``traffic["records"]`` pre-masked instances from ``seed`` into the
    record file ``path``; returns the plain numpy reading of the file."""
    instances = traffic_gen.train_batch(
        dict(traffic, per_chip_batch=traffic["records"]), seed, vocab, 1)
    write_records(path, instances)
    table = read_records_plain(path, len(instances[0]),
                               sum(a.shape[1] for a in instances))
    if not np.array_equal(table, np.concatenate(instances, axis=1)):
        raise RuntimeError("the record file does not hold what was written")
    return table


def write_records(path, arrays):
    """Record ``i`` is row ``i`` of each int32 array, one after another."""
    from incubator_mxnet_tpu import recordio

    rows = np.ascontiguousarray(np.concatenate(arrays, axis=1), "<i4")
    index = os.path.splitext(path)[0] + ".idx"
    with recordio.MXIndexedRecordIO(index, path, "w") as out:
        for i, row in enumerate(rows):
            out.write_idx(i, row.tobytes())


def read_records_plain(path, n, words):
    """The file's ``n`` records of ``words`` int32 each as one ``[n, words]``
    array, from the RecordIO framing alone (magic, length, payload) and
    without the program's reader."""
    raw = np.fromfile(path, "<u4")
    if raw.size != n * (2 + words):
        raise RuntimeError(f"{path} holds {raw.size} words, not {n} records")
    raw = raw.reshape(n, 2 + words)
    if not ((raw[:, 0] == RECORDIO_MAGIC).all() and (raw[:, 1] == 4 * words).all()):
        raise RuntimeError(f"{path} is not {n} whole records of {4 * words} B")
    return raw[:, 2:].astype(np.int32)


def decoder(widths):
    """A record's bytes -> its arrays, for ``Dataset.transform``."""
    ends = np.cumsum(widths).tolist()
    cuts = list(zip([0] + ends[:-1], ends))

    def decode(record):
        words = np.frombuffer(record, "<i4")
        return tuple(words[i:j] for i, j in cuts)

    return decode


class SeededShuffle:
    """``gluon.data.RandomSampler`` on a generator of its own, seeded from
    ``--seed``: another permutation each time the loader is iterated."""

    def __init__(self, length, seed):
        self._length = length
        self._rng = np.random.RandomState(int(seed) % (2 ** 32))

    def __iter__(self):
        return iter(self._rng.permutation(self._length).tolist())

    def __len__(self):
        return self._length


def feed_matches_file(batches, table, key_words, first, per_epoch):
    """Every row of every delivered batch is a record of the file, whole -
    its arrays together, found by the token row's bytes - and inside one
    epoch no record comes twice.  ``batches``: lists of host arrays in the
    record's order; ``first``: the stream's batches before ``batches[0]``."""
    where = {row[:key_words].tobytes(): i for i, row in enumerate(table)}
    seen = set()
    for k, arrays in enumerate(batches):
        if (first + k) % per_epoch == 0:
            seen = set()
        rows = np.concatenate([a.reshape(a.shape[0], -1) for a in arrays], axis=1)
        for row in rows:
            i = where.get(row[:key_words].tobytes())
            if i is None or i in seen or not np.array_equal(row, table[i]):
                return False
            seen.add(i)
    return True
