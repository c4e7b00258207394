#!/usr/bin/env python
"""ResNet-50 training-step HBM byte accounting (the VERDICT r4 roofline
proof): enumerate every feature map in resnet50_v1 at a given batch size,
count the minimum HBM traffic a conv+BN+ReLU training step must move, and
compare the implied bandwidth-bound step time against the measured one.

Traffic model per conv→BN→ReLU unit (bf16 activations), counting only
feature-map traffic (weights are ~25M params ≈ 50 MB bf16, noise at B=256):

  forward:  conv writes out (W) · BN stats read (R) · BN normalize
            read+write (R+W) · next-op read (R)           = 3R + 2W
  backward: d(out) write+read (W+R) · saved normalized act read for dgamma/
            dbeta+dx (R) · conv dgrad reads d(out) (counted above) and
            writes d(in) (= next unit's d(out), counted there) · wgrad
            reads saved input act (R)                      = 2R + 1W
            BN bwd second pass read (R)                    = 1R

  ≈ 6R + 3W  = 9 passes over each feature map per step (conservative:
  XLA's fusion can shave the normalize read by fusing into the consumer,
  and the one-pass stats trick already removed one stats pass).

Maxpool/residual-add/loss-head traffic is counted separately below.

Pure arithmetic (touches no device):
    python tools/resnet_roofline.py [BATCH] [DEVICE_KIND]
with the peaks looked up in ``tools/device_peaks.py`` by ``DEVICE_KIND``
(default "TPU v5 lite"); a device not in that table is an error.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from device_peaks import peaks_for  # noqa: E402


def feature_maps(B):
    """(name, elements) for every conv output in resnet50_v1 at batch B."""
    maps = [("conv0", B * 64 * 112 * 112)]
    cfg = [(3, 64, 56), (4, 128, 28), (6, 256, 14), (3, 512, 7)]
    for si, (blocks, f, hw) in enumerate(cfg, start=1):
        for b in range(blocks):
            maps.append((f"s{si}b{b}_c1", B * f * hw * hw))
            maps.append((f"s{si}b{b}_c2", B * f * hw * hw))
            maps.append((f"s{si}b{b}_c3", B * 4 * f * hw * hw))
            if b == 0:
                maps.append((f"s{si}b{b}_sc", B * 4 * f * hw * hw))
    return maps


def main():
    B = int(sys.argv[1]) if len(sys.argv) > 1 else 256
    device_kind = sys.argv[2] if len(sys.argv) > 2 else "TPU v5 lite"
    peaks = peaks_for(device_kind)  # unknown device = error
    HBM = peaks["hbm_bytes_per_s"]
    bf16 = 2

    maps = feature_maps(B)
    conv_el = sum(e for _, e in maps)
    # residual adds: 4 stages' block outputs (read two, write one) ≈ 3
    # passes over each block's 4f map
    res_el = sum(e for n, e in maps if n.endswith("_c3"))
    pool_el = B * 64 * 56 * 56

    res_bytes = res_el * bf16 * 3 * 2        # fwd add + bwd split
    pool_bytes = pool_el * bf16 * 4          # fwd R/W + bwd select-scatter
    # optimizer: 25.6M params, fp32 momentum R/W + weight R/W + bf16 grad
    opt_bytes = 25.6e6 * (4 * 4 + 2 * 2)

    print(f"B={B}: {conv_el / B / 1e6:.1f}M conv-out elements/img "
          f"({len(maps)} feature maps)")
    # bracket the roofline between an optimistic (9-pass) and realistic
    # (11-pass: BN backward's two fused passes over both dy and x_hat)
    # per-feature-map traffic model
    for passes, label in ((9, "optimistic"), (11, "realistic")):
        conv_bytes = conv_el * bf16 * passes
        total = conv_bytes + res_bytes + pool_bytes + opt_bytes
        t_bw = total / HBM
        print(f"[{label}: {passes} passes/map] conv+BN "
              f"{conv_bytes / 1e9:.1f} GB + residual {res_bytes / 1e9:.1f} "
              f"+ pool {pool_bytes / 1e9:.1f} + opt {opt_bytes / 1e9:.1f} "
              f"= {total / 1e9:.1f} GB/step  -> floor "
              f"{t_bw * 1e3:.1f} ms ({B / t_bw:.0f} img/s)")
    # MXU floor: 12.3 GFLOP/img fwd+bwd (3x fwd 4.1) at the bf16 peak
    t_mxu = B * 12.3e9 / peaks["bf16_flops_per_s"]
    print(f"[{device_kind}] MXU-bound floor: {t_mxu * 1e3:.1f} ms ({B / t_mxu:.0f} img/s) "
          f"-> bandwidth-bound by ~5x at this batch")


if __name__ == "__main__":
    main()
