#!/usr/bin/env python
"""Headline benchmark — BERT-base pretraining throughput (samples/sec).

One fused SPMD train step (forward + backward + Adam, donated buffers) via
``parallel.SPMDTrainer`` on the local mesh: config 3 of BASELINE.md.  Model
init runs on the host CPU device (one eager forward for deferred shapes —
hundreds of tiny per-op compiles are cheaper there), then parameters are
device_put onto the accelerator mesh and every step is a single jitted
program.

Prints ONE JSON line:
  {"metric": "bert_base_samples_per_sec", "value": N, "unit":
   "samples/sec/chip", "vs_baseline": N, "platform": "tpu",
   "device_kind": "...", "n_devices": N}

The numbers are device metrics, so the bench needs an accelerator: with
none it exits non-zero naming the platform it found, and any failure exits
non-zero with its traceback.  ``--dry-run-cpu`` is the explicit CPU
rehearsal (pins JAX to the CPU, two steps): it proves the path runs and
prints ``"value": null, "dry_run": true`` — never a rate.

vs_baseline divides by 100 samples/sec/device — recalled MXNet-era
GluonNLP BERT-base (seq 128, fp16) per-V100 pretraining throughput
(UNVERIFIED: reference mount was empty; see BASELINE.md provenance note).

``MXNET_TPU_BENCH=resnet50`` switches to BASELINE.md config 2 (ResNet-50
ImageNet-shape training, synthetic data, bf16 AMP, SGD+momentum);
vs_baseline there divides by 1400 img/s — recalled MXNet-era fp16 V100
throughput (same provenance caveat).
"""
import json
import os
import sys
import time

import numpy as np

BASELINE_SAMPLES_PER_SEC = 100.0
BASELINE_RESNET50_IMG_PER_SEC = 1400.0


DRY_RUN_FLAG = "--dry-run-cpu"


def _setup(dry_run):
    """First touch of JAX: pin the CPU for a dry run, place the compile
    cache, and refuse to time a CPU under a device metric's name.  Returns
    the run description every bench function and record carries."""
    import jax

    if dry_run:
        jax.config.update("jax_platforms", "cpu")
    import incubator_mxnet_tpu as mx

    mx.config.enable_compile_cache()
    device = mx.config.device_record()
    if device["platform"] == "cpu" and not dry_run:
        raise SystemExit(
            f"bench.py: no accelerator — jax.devices()[0].platform is "
            f"{device['platform']!r}; these are device metrics "
            f"({DRY_RUN_FLAG} rehearses the path on the CPU)")
    return {"dry_run": dry_run, "device": device}


def _window(run, warmup, steps):
    """(warmup, steps) of the timed window; a dry run takes two steps."""
    if run["dry_run"]:
        warmup, steps = 1, 2
    return warmup, int(os.environ.get("MXNET_TPU_BENCH_STEPS", steps))


def _host_init_scope():
    """Eager model init on the host CPU device (``mx.cpu()`` resolves to
    the default device when the CPU platform is excluded, e.g.
    ``JAX_PLATFORMS=tpu`` — init is then merely slower)."""
    import jax

    import incubator_mxnet_tpu as mx

    return jax.default_device(mx.cpu().jax_device())


def _record(run, metric, value, unit, baseline, **extra):
    """The ONE output line.  A dry run has no rate to report."""
    if run["dry_run"]:
        out = {"metric": metric, "value": None, "unit": unit,
               "vs_baseline": None, "dry_run": True}
    else:
        out = {"metric": metric, "value": round(value, 2), "unit": unit,
               "vs_baseline": round(value / baseline, 3)}
    out.update(extra)
    out.update(run["device"])
    print(json.dumps(out))


def _fence(trainer, loss):
    """End the timed region in a real D2H of the last loss AND one updated
    parameter: dispatch is asynchronous, and the loss alone doesn't depend
    on the final optimizer update — fencing a param covers it."""
    import jax
    import numpy as np

    float(np.asarray(loss._data))
    p0 = jax.tree_util.tree_leaves(trainer._param_arrays)[0]
    np.asarray(p0.addressable_data(0))


def _bench_bert_folded(run, net, mlm_loss, mp, B, P, steps, warmup,
                       tok, seg, pos, labels, k=1):
    """bert_base through gluon.Trainer.fold_step (MXNET_STEP_FOLD=1): one
    donated compiled program per step on the default device — the folded
    twin of the SPMD headline, so the two paths are comparable round to
    round (docs/step_fold.md).  With k > 1 (MXNET_STEP_FOLD_K=K) the step
    is ``Trainer.fold_steps``: the batch is tiled to a [K, B, ...] window
    and one dispatch runs K logical steps in an in-program scan —
    samples/sec still counts LOGICAL steps, so the number is directly
    comparable to the K=1 and SPMD headlines."""
    import jax
    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon

    dev = jax.devices()[0]

    def to_dev(nd):
        nd._data = jax.device_put(nd._data, dev)
        return nd

    # params/batch were staged on the CPU device for cheap eager init;
    # the fold runs where the chips are
    for p in net.collect_params().values():
        p._data._data = jax.device_put(p._data._data, dev)
        if p._data._grad is not None:
            p._data._grad._data = jax.device_put(p._data._grad._data, dev)
    nds = (tok, seg, pos, labels) if P else (tok, seg, labels)
    if k > 1:
        # [K, B, ...] stacked window — the io.DataPipeline.stage_window
        # layout; one tiled resident batch keeps H2D off the loop just
        # like the SPMD path's pre-staged shard
        batch = [to_dev(mx.nd.array(
            np.repeat(np.asarray(a._data)[None], k, axis=0),
            dtype=str(a._data.dtype))) for a in nds]
    else:
        batch = [to_dev(a) for a in nds]

    trainer = gluon.Trainer(
        net.collect_params(), "adam",
        {"learning_rate": 1e-4, "multi_precision": mp}, kvstore=None)
    if P:
        loss_fn = lambda t, s, pm, lb: mlm_loss(net(t, s, pm), lb)
    else:
        loss_fn = lambda t, s, lb: mlm_loss(net(t, s), lb)
    fold = (trainer.fold_steps(loss_fn, k=k, block=net) if k > 1
            else trainer.fold_step(loss_fn, block=net))
    variant = "step_fold" if k <= 1 else f"step_fold_k[{k}]"

    def fence(loss):
        float(np.asarray(loss._data).mean())
        p0 = next(iter(net.collect_params().values()))
        np.asarray(p0._data._data)

    for _ in range(max(1, warmup // max(1, k))):
        loss = fold(*batch)
    fence(loss)
    if not fold.folded:
        # do NOT time and emit a headline: it would be the EAGER path's
        # number wearing the step_fold variant tag
        raise RuntimeError(f"{variant}: fold fell back to the eager path: "
                           f"{fold.fallback_reason}")
    n_windows = max(1, steps // max(1, k))
    t0 = time.perf_counter()
    for _ in range(n_windows):
        loss = fold(*batch)
    fence(loss)
    dt = time.perf_counter() - t0
    # per LOGICAL step: a K-window is K steps of B samples
    samples_per_sec = B * n_windows * max(1, k) / dt
    extra = {"variant": variant, "folded": True}
    if k > 1:
        extra["k"] = k
    # the fold runs on ONE device whatever the host holds
    _record(run, "bert_base_samples_per_sec", samples_per_sec,
            "samples/sec/chip", BASELINE_SAMPLES_PER_SEC, **extra)


def bench_resnet50(run):
    """ResNet-50 training throughput, synthetic ImageNet-shape data (the
    ``--benchmark 1`` mode of the reference's train_imagenet fit loop)."""
    import jax

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray
    from incubator_mxnet_tpu.parallel import make_mesh, SPMDTrainer

    B = int(os.environ.get("MXNET_TPU_BENCH_BATCH", "256"))
    warmup, steps = _window(run, 2, 60)

    from incubator_mxnet_tpu import amp
    if os.environ.get("MXNET_TPU_BENCH_AMP", "1") == "1":
        amp.init("bfloat16")

    with _host_init_scope():
        mx.random.seed(0)
        net = resnet50_v1(classes=1000)
        net.initialize()
        rng = np.random.RandomState(0)
        img = mx.nd.array(rng.rand(B, 3, 224, 224).astype(np.float32))
        labels = mx.nd.array(rng.randint(0, 1000, (B,)), dtype="int32")
        # materialize deferred-init shapes with a tiny batch (param shapes
        # are batch-independent; a full-B eager CPU forward takes minutes)
        net(mx.nd.zeros((2, 3, 224, 224)))

    def ce_loss(out, label):
        from incubator_mxnet_tpu.ops.nn import streaming_softmax_ce
        logits = out._data if hasattr(out, "_data") else out[0]._data
        return NDArray(streaming_softmax_ce(logits, label._data))  # [B]

    # bf16 canonical params + fp32 SGD-momentum masters: measured SLOWER
    # than fp32 params for ResNet (2423 vs 2455 img/s) — the mp master
    # round-trip costs more than the per-use weight cast it replaces at
    # conv-sized weights, and BN running stats lose precision.  Default
    # off; the knob remains for A/B.
    mp = (os.environ.get("MXNET_TPU_BENCH_BF16_PARAMS", "0") == "1"
          and os.environ.get("MXNET_TPU_BENCH_AMP", "1") == "1")
    if mp:
        net.cast("bfloat16")

    mesh = make_mesh()
    trainer = SPMDTrainer(net, ce_loss, "sgd",
                          {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4,
                           "multi_precision": mp},
                          mesh=mesh)

    # pre-stage the synthetic batch on the mesh (the reference's
    # --benchmark 1 discipline; per-step H2D belongs to the input
    # pipeline, measured separately)
    img, labels = trainer.shard_batch(img, labels)

    prof_dir = os.environ.get("MXNET_TPU_BENCH_PROFILE")
    if prof_dir:
        for _ in range(2):
            loss = trainer.step(img, labels)
        _fence(trainer, loss)
        with jax.profiler.trace(prof_dir):
            for _ in range(5):
                loss = trainer.step(img, labels)
            _fence(trainer, loss)

    dt = _run_spmd(trainer, img, labels, warmup, steps)
    _emit(run, "resnet50_img_per_sec", B * steps / dt, "img/sec/chip",
          BASELINE_RESNET50_IMG_PER_SEC, mesh)


def _run_spmd(trainer, inputs, labels, warmup, steps):
    """Time `steps` optimizer steps, fenced at both ends."""
    import time as _t

    for _ in range(warmup):
        loss = trainer.step(inputs, labels)
    _fence(trainer, loss)
    t0 = _t.perf_counter()
    for _ in range(steps):
        loss = trainer.step(inputs, labels)
    _fence(trainer, loss)
    return _t.perf_counter() - t0


def _emit(run, metric, total_per_sec, unit, baseline, mesh):
    """Emit per-CHIP throughput: SPMD shards the global batch across the
    mesh, so total/dt must be divided by the chip count (as the resnet50
    and BERT benches always did)."""
    _record(run, metric, total_per_sec / mesh.devices.size, unit, baseline)


def bench_mnist(run, model="mlp"):
    """BASELINE config 1: MLP / LeNet on MNIST-shape data (the reference's
    train_mnist.py).  vs_baseline divides by 50k samples/s — recalled
    MXNet-era V100 MLP-MNIST throughput (UNVERIFIED, same provenance
    caveat as the other baselines)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray
    from incubator_mxnet_tpu.ops.nn import streaming_softmax_ce
    from incubator_mxnet_tpu.parallel import make_mesh, SPMDTrainer

    B = int(os.environ.get("MXNET_TPU_BENCH_BATCH", "1024"))
    warmup, steps = _window(run, 3, 60)
    from incubator_mxnet_tpu import amp
    if os.environ.get("MXNET_TPU_BENCH_AMP", "1") == "1":
        amp.init("bfloat16")
    with _host_init_scope():
        mx.random.seed(0)
        net = nn.HybridSequential()
        if model == "mlp":
            net.add(nn.Dense(128, activation="relu"),
                    nn.Dense(64, activation="relu"), nn.Dense(10))
            img = mx.nd.array(np.random.RandomState(0).rand(B, 784).astype(np.float32))
            net.initialize()
            net(mx.nd.zeros((2, 784)))
        else:  # lenet
            net.add(nn.Conv2D(20, 5, activation="tanh"), nn.MaxPool2D(2, 2),
                    nn.Conv2D(50, 5, activation="tanh"), nn.MaxPool2D(2, 2),
                    nn.Flatten(), nn.Dense(500, activation="tanh"), nn.Dense(10))
            img = mx.nd.array(np.random.RandomState(0).rand(B, 1, 28, 28).astype(np.float32))
            net.initialize()
            net(mx.nd.zeros((2, 1, 28, 28)))
        labels = mx.nd.array(np.random.RandomState(0).randint(0, 10, (B,)), dtype="int32")

    def loss_fn(out, label):
        logits = out._data if hasattr(out, "_data") else out[0]._data
        return NDArray(streaming_softmax_ce(logits, label._data))

    trainer = SPMDTrainer(net, loss_fn, "sgd",
                          {"learning_rate": 0.1, "momentum": 0.9}, mesh=make_mesh())
    img, labels = trainer.shard_batch(img, labels)
    dt = _run_spmd(trainer, img, labels, warmup, steps)
    _emit(run, f"mnist_{model}_samples_per_sec", B * steps / dt, "samples/sec/chip",
          50000.0, trainer.mesh)


def bench_transformer(run):
    """BASELINE config 4: Transformer-big WMT-shape training.  vs_baseline
    divides by 4500 tokens/s — recalled fp16 V100 transformer-big
    throughput (UNVERIFIED recall)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import transformer_big
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray
    from incubator_mxnet_tpu.ops.nn import streaming_softmax_ce
    from incubator_mxnet_tpu.parallel import make_mesh, SPMDTrainer

    # S=256 default: the WMT bucketed pipeline's dominant bucket (the
    # round-3 S=64 config flattered tokens/s and starved the MXU —
    # VERDICT r3 item 3).  MXNET_TPU_BENCH_SEQ overrides for probes.
    B = int(os.environ.get("MXNET_TPU_BENCH_BATCH", "32"))
    S = int(os.environ.get("MXNET_TPU_BENCH_SEQ", "256"))
    vocab = 32768
    warmup, steps = _window(run, 3, 120)
    from incubator_mxnet_tpu import amp
    if os.environ.get("MXNET_TPU_BENCH_AMP", "1") == "1":
        amp.init("bfloat16")
    with _host_init_scope():
        mx.random.seed(0)
        net = transformer_big(vocab_size=vocab, max_length=512, dropout=0.1)
        net.initialize()
        rng = np.random.RandomState(0)
        src = mx.nd.array(rng.randint(0, vocab, (B, S)), dtype="int32")
        tgt = mx.nd.array(rng.randint(0, vocab, (B, S)), dtype="int32")
        labels = mx.nd.array(rng.randint(0, vocab, (B, S)), dtype="int32")
        net(mx.nd.zeros((2, S), dtype="int32"), mx.nd.zeros((2, S), dtype="int32"))

    # same bf16-canonical-params + fp32-master discipline as the BERT bench
    mp = (os.environ.get("MXNET_TPU_BENCH_BF16_PARAMS", "1") == "1"
          and os.environ.get("MXNET_TPU_BENCH_AMP", "1") == "1")
    if mp:
        net.cast("bfloat16")

    def loss_fn(out, label):
        return NDArray(streaming_softmax_ce(out._data, label._data).mean(axis=-1))

    trainer = SPMDTrainer(net, loss_fn, "adam",
                          {"learning_rate": 1e-4, "multi_precision": mp},
                          mesh=make_mesh())
    src, tgt, labels = trainer.shard_batch(src, tgt, labels)
    dt = _run_spmd(trainer, (src, tgt), labels, warmup, steps)
    tok_per_sec = 2 * B * S * steps / dt  # src+tgt tokens, the WMT convention
    _emit(run, "transformer_big_tokens_per_sec", tok_per_sec, "tokens/sec/chip",
          4500.0, trainer.mesh)


def bench_ssd(run):
    """BASELINE config 5: SSD-512 detection training (dynamic-shape stress;
    here fixed-shape by design).  vs_baseline divides by 60 img/s —
    recalled fp16 V100 SSD-512 throughput (UNVERIFIED recall)."""
    import jax.numpy as jnp

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.model_zoo.ssd import ssd_512_resnet18
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray
    from incubator_mxnet_tpu.ops.detection import multibox_target
    from incubator_mxnet_tpu.ops.nn import streaming_softmax_ce
    from incubator_mxnet_tpu.parallel import make_mesh, SPMDTrainer

    B = int(os.environ.get("MXNET_TPU_BENCH_BATCH", "32"))
    warmup, steps = _window(run, 2, 60)
    from incubator_mxnet_tpu import amp
    if os.environ.get("MXNET_TPU_BENCH_AMP", "1") == "1":
        amp.init("bfloat16")
    backbone = os.environ.get("MXNET_TPU_BENCH_SSD_BACKBONE", "resnet18")
    if backbone not in ("resnet18", "vgg16"):
        raise ValueError(f"MXNET_TPU_BENCH_SSD_BACKBONE must be resnet18 or vgg16, got {backbone!r}")
    with _host_init_scope():
        mx.random.seed(0)
        if backbone == "vgg16":
            from incubator_mxnet_tpu.gluon.model_zoo.ssd import ssd_512_vgg16_atrous
            net = ssd_512_vgg16_atrous(num_classes=20)
        else:
            net = ssd_512_resnet18(num_classes=20)
        net.initialize()
        rng = np.random.RandomState(0)
        img = mx.nd.array(rng.rand(B, 3, 512, 512).astype(np.float32))
        lab = np.full((B, 4, 5), -1, np.float32)
        lab[:, 0] = [1, 0.2, 0.2, 0.7, 0.7]
        lab[:, 1] = [5, 0.5, 0.5, 0.9, 0.9]
        labels = mx.nd.array(lab)
        net(mx.nd.zeros((2, 3, 512, 512)))

    def ssd_loss(out, label):
        anchors, cls_preds, box_preds = out
        bt, bm, ct = multibox_target(anchors._data, label._data,
                                     jnp.swapaxes(cls_preds._data, 1, 2))
        ce = streaming_softmax_ce(cls_preds._data, ct).mean(axis=-1)
        l1 = (jnp.abs(box_preds._data - bt) * bm).mean(axis=-1)
        return NDArray(ce + l1)

    trainer = SPMDTrainer(net, ssd_loss, "sgd",
                          {"learning_rate": 0.01, "momentum": 0.9, "wd": 5e-4},
                          mesh=make_mesh())
    img, labels = trainer.shard_batch(img, labels)
    dt = _run_spmd(trainer, img, labels, warmup, steps)
    _emit(run, f"ssd512_{backbone}_img_per_sec" if backbone != "resnet18" else "ssd512_img_per_sec", B * steps / dt, "img/sec/chip", 60.0, trainer.mesh)


def bench_yolo3(run):
    """Extra (non-BASELINE) config: YOLOv3-darknet53 detection training at
    416², the canonical COCO setup.  vs_baseline divides by 55 img/s —
    recalled fp16 V100 YOLOv3 training throughput (UNVERIFIED recall)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.model_zoo import yolo
    from incubator_mxnet_tpu import ndarray as nd
    from incubator_mxnet_tpu.parallel import make_mesh, SPMDTrainer

    B = int(os.environ.get("MXNET_TPU_BENCH_BATCH", "16"))
    warmup, steps = _window(run, 2, 20)
    from incubator_mxnet_tpu import amp
    if os.environ.get("MXNET_TPU_BENCH_AMP", "1") == "1":
        amp.init("bfloat16")
    C = 80
    with _host_init_scope():
        mx.random.seed(0)
        net = yolo.yolo3_darknet53(num_classes=C)
        net.initialize()
        rng = np.random.RandomState(0)
        img = mx.nd.array(rng.rand(B, 3, 416, 416).astype(np.float32))
        lab = np.full((B, 8, 5), -1, np.float32)
        lab[:, 0] = [1, 80, 80, 280, 280]
        lab[:, 1] = [7, 200, 120, 380, 360]
        labels = mx.nd.array(lab)
        net(mx.nd.zeros((2, 3, 416, 416)))

    def yolo_loss(out, label):
        preds, off, anc, st = out
        gt_ids = nd.slice_axis(label, axis=-1, begin=0, end=1)
        gt_boxes = nd.slice_axis(label, axis=-1, begin=1, end=5)
        targets = yolo.yolo3_targets(gt_boxes, gt_ids, off, anc, st, C)
        return yolo.yolo3_loss(preds, *targets, C, reduction="none")

    trainer = SPMDTrainer(net, yolo_loss, "sgd",
                          {"learning_rate": 1e-3, "momentum": 0.9, "wd": 5e-4},
                          mesh=make_mesh())
    img, labels = trainer.shard_batch(img, labels)
    dt = _run_spmd(trainer, img, labels, warmup, steps)
    _emit(run, "yolo3_416_img_per_sec", B * steps / dt, "img/sec/chip", 55.0, trainer.mesh)


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if [a for a in argv if a != DRY_RUN_FLAG]:
        raise SystemExit(f"usage: bench.py [{DRY_RUN_FLAG}]  "
                         "(configs are selected by MXNET_TPU_BENCH=)")
    run = _setup(dry_run=DRY_RUN_FLAG in argv)
    mode = os.environ.get("MXNET_TPU_BENCH")
    if mode == "resnet50":
        return bench_resnet50(run)
    if mode == "yolo3":
        return bench_yolo3(run)
    if mode in ("mnist", "mlp"):
        return bench_mnist(run, "mlp")
    if mode == "lenet":
        return bench_mnist(run, "lenet")
    if mode == "transformer":
        return bench_transformer(run)
    if mode == "ssd":
        return bench_ssd(run)
    return bench_bert(run)


def build_bert_pretrain(B, S=128, P=20, vocab=30522, amp_bf16=True,
                        bf16_params=True, bert_kwargs=None):
    """The BERT pretraining workload both ``bench.py`` and
    ``chip_smoke.py`` run: model, one synthetic batch, loss.  Returns
    ``(net, (tok, seg, pos), labels, mlm_loss, mp)`` (``pos`` is None
    when ``P == 0``).  ``bert_kwargs`` swaps BERT-base for a
    ``BERTModel(**bert_kwargs)`` (the smoke's tiny CPU rehearsal)."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import amp
    from incubator_mxnet_tpu.gluon.model_zoo.bert import (
        BERTForPretrain, BERTModel, bert_base)
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray

    # BASELINE.md config 3 is mixed-precision: bf16 matmuls (MXU-native)
    # with fp32 softmax/norms/optimizer state, via the mx.amp op lists.
    if amp_bf16:
        amp.init("bfloat16")

    with _host_init_scope():
        mx.random.seed(0)
        if bert_kwargs is None:
            bert = bert_base(vocab_size=vocab, max_length=512, dropout=0.1)
        else:
            bert = BERTModel(vocab_size=vocab, dropout=0.1, **bert_kwargs)
        net = BERTForPretrain(bert, vocab_size=vocab)
        net.initialize()
        rng = np.random.RandomState(0)
        tok = mx.nd.array(rng.randint(0, vocab, (B, S)), dtype="int32")
        seg = mx.nd.zeros((B, S), dtype="int32")
        if P:
            pos = mx.nd.array(
                np.sort(np.stack([rng.choice(S, P, replace=False) for _ in range(B)])),
                dtype="int32")
            labels = mx.nd.array(rng.randint(0, vocab, (B, P)), dtype="int32")
        else:
            pos = None
            labels = mx.nd.array(rng.randint(0, vocab, (B, S)), dtype="int32")
        # materialize deferred-init shapes with a tiny batch (cheap on the
        # eager CPU path; param shapes are batch-independent)
        net(mx.nd.zeros((2, S), dtype="int32"), mx.nd.zeros((2, S), dtype="int32"),
            mx.nd.zeros((2, P), dtype="int32") if P else None)

    # Store the canonical parameters in bf16 with fp32 Adam master weights
    # (MLPerf BERT discipline).  With fp32 params, every weight pays a
    # fp32-read + bf16-write AMP cast per step AND wgrad outputs convert
    # back to fp32; bf16 params + mp_adam_update cut ~10 bytes/param/step
    # of pure HBM traffic.
    mp = bf16_params and amp_bf16
    if mp:
        net.cast("bfloat16")

    def mlm_loss(out, label):
        # Streaming cross-entropy: no [B, S, V] fp32 log-prob tensor is
        # materialized (profiled: the log_softmax form cost ~3 ms/step in
        # HBM traffic at B=64 — docs/PERF_NOTES.md).
        from incubator_mxnet_tpu.ops.nn import streaming_softmax_ce
        mlm_logits, _ = out
        return NDArray(streaming_softmax_ce(mlm_logits._data, label._data).mean(axis=-1))

    return net, (tok, seg, pos), labels, mlm_loss, mp


def bench_bert(run):
    """BASELINE config 3 — the headline: BERT-base pretraining."""
    import jax

    from incubator_mxnet_tpu.parallel import make_mesh, SPMDTrainer

    B = int(os.environ.get("MXNET_TPU_BENCH_BATCH", "64"))
    # MLM decodes only the masked positions (GluonNLP masked_positions /
    # MLPerf max_predictions_per_seq=20 at S=128) — the vocab projection
    # runs on P=20 tokens, not all 128; MXNET_TPU_BENCH_ALL_POSITIONS=1
    # restores the decode-everything variant for comparison.
    P = 0 if os.environ.get("MXNET_TPU_BENCH_ALL_POSITIONS") == "1" else 20
    # 180-step window: the fence's fixed D2H round-trip is measurement
    # cost, not workload, and a long window amortizes it.
    warmup, steps = _window(run, 3, 180)

    # MXNET_TPU_BENCH_AMP=0 / MXNET_TPU_BENCH_BF16_PARAMS=0 restore fp32
    net, (tok, seg, pos), labels, mlm_loss, mp = build_bert_pretrain(
        B, P=P,
        amp_bf16=os.environ.get("MXNET_TPU_BENCH_AMP", "1") == "1",
        bf16_params=os.environ.get("MXNET_TPU_BENCH_BF16_PARAMS", "1") == "1")

    fold_k = int(os.environ.get("MXNET_STEP_FOLD_K", "0") or 0)
    if os.environ.get("MXNET_STEP_FOLD") == "1" or fold_k > 1:
        # ISSUE 15: route the headline through the FOLDED imperative step
        # (gluon.Trainer.fold_step — one donated compiled program per
        # step on a single device, docs/step_fold.md) so the TPU round
        # measures the fold against the SPMD path.  ISSUE 17: with
        # MXNET_STEP_FOLD_K=K>1 the step is the K-step fold_steps scan —
        # one dispatch per K logical steps on a [K, B, ...] tiled batch.
        return _bench_bert_folded(run, net, mlm_loss, mp, B, P, steps, warmup,
                                  tok, seg, pos, labels,
                                  k=max(1, fold_k))
    mesh = make_mesh()  # pure-dp over whatever local devices exist
    trainer = SPMDTrainer(net, mlm_loss, "adam",
                          {"learning_rate": 1e-4, "multi_precision": mp}, mesh=mesh)

    # Pre-stage the synthetic batch on the mesh (the reference's
    # --benchmark 1 mode reuses one device-resident batch the same way:
    # [U:example/image-classification/common/fit.py]); keeps per-step H2D
    # off the critical path, as a prefetching input pipeline would.
    if P:
        tok, seg, pos, labels = trainer.shard_batch(tok, seg, pos, labels)
        inputs = (tok, seg, pos)
    else:
        tok, seg, labels = trainer.shard_batch(tok, seg, labels)
        inputs = (tok, seg)

    for _ in range(warmup):
        loss = trainer.step(inputs, labels)
    _fence(trainer, loss)

    prof_dir = os.environ.get("MXNET_TPU_BENCH_PROFILE")
    if prof_dir:
        with jax.profiler.trace(prof_dir):
            for _ in range(5):
                loss = trainer.step(inputs, labels)
            _fence(trainer, loss)

    t0 = time.perf_counter()
    for _ in range(steps):
        loss = trainer.step(inputs, labels)
    _fence(trainer, loss)
    dt = time.perf_counter() - t0

    _emit(run, "bert_base_samples_per_sec", B * steps / dt,
          "samples/sec/chip", BASELINE_SAMPLES_PER_SEC, mesh)


if __name__ == "__main__":
    main()
