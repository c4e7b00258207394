#!/usr/bin/env python
"""Long-context attention benchmark — the exceeds-reference capability
(SURVEY §5): blockwise Pallas flash fwd+bwd keeps memory linear in S
where the XLA path's S×S buffers blow up.

Times fwd+bwd (jax.grad) of causal attention at growing S, the
Mosaic-compiled Pallas kernels vs XLA.  Device timings: runs on a TPU only
(through the chip tool) and exits non-zero anywhere else:

    python tools/bench_longcontext.py

The crossover lengths in ops/attention.py predate PRs 2-19; on this code
they are not measured.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from incubator_mxnet_tpu import config
    from incubator_mxnet_tpu.ops import attention as att

    device = config.device_record()
    if device["platform"] != "tpu":
        raise SystemExit(f"bench_longcontext.py times Mosaic kernels: needs "
                         f"a TPU, found platform {device['platform']!r}")
    config.enable_compile_cache()
    print(f"device: {device}")
    B, H, D = 1, 8, 64
    print(f"{'S':>7}{'mode':>9}{'fwd+bwd(ms)':>14}{'tokens/s':>12}")
    for S in (4096, 8192, 16384, 32768):
        q = jnp.asarray(np.random.RandomState(0).randn(B, H, S, D)).astype(jnp.bfloat16)
        for mode in ("pallas", "xla"):
            os.environ["MXNET_TPU_FLASH"] = "on" if mode == "pallas" else "off"
            # thresholds are read at import; force the gate decisions
            att._PALLAS_FWD_MIN_SEQ = 0 if mode == "pallas" else 10 ** 9
            att._PALLAS_BWD_MIN_SEQ = 0 if mode == "pallas" else 10 ** 9

            def loss(x):
                return (att.flash_attention(x, x, x, causal=True) ** 2
                        ).sum().astype(jnp.float32)

            try:
                g = jax.jit(jax.grad(loss))
                jax.block_until_ready(g(q))  # compile + smoke
                t0 = time.perf_counter()
                for _ in range(5):
                    out = g(q)
                np.asarray(out[0, 0, 0])  # concrete D2H fence
                dt = (time.perf_counter() - t0) / 5
                print(f"{S:>7}{mode:>9}{dt*1e3:>14.1f}{B*S/dt:>12.0f}")
            except Exception as e:
                print(f"{S:>7}{mode:>9}{'FAILED: ' + type(e).__name__:>14}")


if __name__ == "__main__":
    main()
