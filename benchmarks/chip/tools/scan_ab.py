"""Device time of the two selective scans at a zoo model's widths: the
Pallas kernel (``kernels/mamba_scan.py``) against the chunked XLA scan
(``models.mamba.selective_scan_chunked``), one row, each chunk bucket up
to ``--chunk-size``.

    python3 benchmarks/chip/tools/scan_ab.py --model jamba2-3b \\
        [--chunk-size 512] [--reps 200]

Each timing is ``--reps`` calls chained through the state inside one
jitted loop, after a warm call, on the host clock around
``block_until_ready``.  Prints one JSON line per bucket: microseconds per
call of each scan, the largest difference of their outputs and the
largest output.  Needs the
chip.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", required=True)
    p.add_argument("--chunk-size", type=int, default=512)
    p.add_argument("--reps", type=int, default=200)
    a = p.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from chipbench import harness
    from repro.configs import get_config
    from repro.models import mamba as mm
    harness.device_info(1)
    cfg = get_config(a.model)
    di, n = cfg.ssm_d_inner, cfg.ssm_state
    scans = {"pallas": mm._pallas_scan, "xla": mm.selective_scan_chunked}
    b = 8
    while b <= a.chunk_size:
        k = jax.random.split(jax.random.key(b), 6)
        x = jax.random.normal(k[0], (1, b, di), jnp.bfloat16)
        dt = jax.nn.softplus(jax.random.normal(k[1], (1, b, di)))
        A = -jnp.exp(0.1 * jax.random.normal(k[2], (di, n)))
        bc, cc = (jax.random.normal(kk, (1, b, n), jnp.bfloat16)
                  for kk in k[3:5])
        d = jnp.ones((di,))
        h0 = jnp.zeros((1, di, n), jnp.float32)
        out = {"bucket": b}
        ys = {}
        for name, scan in scans.items():
            def loop(h, scan=scan):
                def body(_, carry):
                    y, h = scan(x, dt, A, bc, cc, d, carry[1])
                    return y, h
                y0 = jnp.zeros(x.shape, x.dtype)
                return jax.lax.fori_loop(0, a.reps, body, (y0, h))
            fn = jax.jit(loop)
            jax.block_until_ready(fn(h0))
            t0 = time.perf_counter()
            ys[name] = jax.block_until_ready(fn(h0))
            out[f"{name}_us"] = (time.perf_counter() - t0) / a.reps * 1e6
        yp, yx = (ys[k][0].astype(jnp.float32) for k in scans)
        out["max_abs_diff_y"] = float(jnp.max(jnp.abs(yp - yx)))
        out["max_abs_y"] = float(jnp.max(jnp.abs(yx)))
        print(json.dumps(out), flush=True)
        b *= 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
