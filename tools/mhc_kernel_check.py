"""The residual path's kernel pair (``paddle_tpu/kernels/pallas/mhc.py``) on
the chip at a served program's shapes: ``pt_mhc_pre`` / ``pt_mhc_post``
against their jnp reference (the maps, ``u`` and the mixed stream), and the
time of a chain of ``--sublayers`` pre / post pairs as a window program runs
them, kernel against composed XLA — us a (token, sublayer) beside the least
the bytes allow (``benchmark/lib/mhc_cost.py``).

    python3 tools/mhc_kernel_check.py [--rows 128,2176] [--hidden 3584]

A kernel timed alone by the host's clock holds the dispatch of its call, and a
chain's intermediates may stay in VMEM (.claude/skills/verify): the op's own
time in a served program is the device trace's (``serve.mhc_roofline_pct``).
Refuses to run without a TPU; its last line is the JSON verdict."""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", default="128,2176")
    ap.add_argument("--hidden", type=int, default=3584)
    ap.add_argument("--streams", type=int, default=4)
    ap.add_argument("--sublayers", type=int, default=10)
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("mhc_kernel_check: no TPU — a kernel's time has no "
                         "CPU mode")
    from benchmark.lib import mhc_cost, peaks
    from paddle_tpu.kernels.pallas import mhc

    n, c = args.streams, args.hidden
    nc = n * c
    kw = dict(n=n, iters=20, eps=1e-6, lo=-30.0, hi=30.0)
    peak = peaks.peaks_for(jax.devices()[0].device_kind)
    out = {"device": jax.devices()[0].device_kind, "rows": {}}
    ok = True
    for t in (int(r) for r in args.rows.split(",")):
        k = jax.random.split(jax.random.PRNGKey(t), 6)
        x = jax.random.normal(k[0], (t, nc), jnp.float32)
        y = jax.random.normal(k[1], (t, c), jnp.float32)
        g = 1 + 0.1 * jax.random.normal(k[2], (nc,))
        phi = jax.random.normal(k[3], (nc, 2 * n + n * n)) / np.sqrt(nc)
        b = jnp.concatenate([jnp.zeros(2 * n), (
            jnp.eye(n) + 0.75 * jax.random.normal(k[4], (n, n))).reshape(-1)])
        proj, bias = mhc.pack_params(g, phi, b, jnp.asarray([1, 1, .5]), n)
        got, want = {}, {}
        for impl, into in (("pallas", got), ("reference", want)):
            pre = jax.jit(lambda x: mhc.mhc_pre(x, proj, bias, impl=impl,
                                                **kw))
            post = jax.jit(lambda x, y, m: mhc.mhc_post(x, y, m, n=n,
                                                        impl=impl))
            into["u"], into["maps"] = pre(x)
            into["x"] = post(x, y, want.get("maps", into["maps"]))
        errs = {key: float(jnp.abs(got[key] - want[key]).max())
                for key in got}
        _p, _q, h_res = mhc.unpack_maps(got["maps"], n)
        errs["rows_sum"] = float(jnp.abs(h_res.sum(-1) - 1).max())
        errs["cols_sum_p99"] = float(jnp.quantile(
            jnp.abs(h_res.sum(-2) - 1).max(-1), 0.99))
        fine = errs["maps"] < 1e-4 and errs["u"] < 1e-3 and errs["x"] < 1e-3
        ok = ok and fine
        row = {"max_abs_err": errs, "parity": fine}
        for impl in ("pallas", "reference"):
            def chain(x, y, impl=impl):
                for _ in range(args.sublayers):
                    u, maps = mhc.mhc_pre(x, proj, bias, impl=impl, **kw)
                    x = mhc.mhc_post(x, y + u, maps, n=n, impl=impl)
                return x

            fn = jax.jit(chain, donate_argnums=0)
            cur = fn(x + 0, y)
            cur.block_until_ready()
            t0 = time.perf_counter()
            for _ in range(args.reps):
                cur = fn(cur, y)
            cur.block_until_ready()
            took = (time.perf_counter() - t0) / args.reps
            mixes = t * args.sublayers
            floor = mhc_cost.floor_seconds(mhc_cost.mix_cost(
                mixes, {"streams": n, "hidden": c, "itemsize": 4}), peak)
            row[impl] = {"ms_a_chain": took * 1e3,
                         "us_a_mix": took / mixes * 1e6,
                         "floor_us_a_mix": floor["seconds"] / mixes * 1e6,
                         "of_floor_pct": 100 * floor["seconds"] / took}
        out["rows"][t] = row
        print(f"mhc rows={t} " + json.dumps(row), flush=True)
    out["ok"] = ok
    print(json.dumps(out), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
