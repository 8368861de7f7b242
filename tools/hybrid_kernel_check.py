"""The two kernels a stack of one-mixer layers runs at shapes no other served
model has, on the chip against ``jnp``:

- ``pt_ssm_step`` (``paddle_tpu/kernels/pallas/ssm_step.py``) at ``[slots,
  64, 64, 128]`` with 8 heads a group (Falcon-H1's is ``[64, 32, 128, 256]``,
  16 a group): parity with its jnp reference, and a donated call's time
  beside the least the bytes allow (``benchmark/lib/ssm_cost.py``);
- the two-matrix grouped matmuls of an ungated expert
  (``nn.layer.moe.moe_held_experts_mlp`` with no gate: megablox at a width no
  128 divides, 1856, stored at 1920 lanes) at a round's and a chunk's rows:
  parity with a dense ``jnp`` loop over the experts, and the time of a layer
  beside ``benchmark/lib/moe_relu2_cost.py``'s floor.

    python3 tools/hybrid_kernel_check.py [--slots 128] [--rows 128,2048]

A kernel timed alone by the host's clock holds the dispatch of its call
(.claude/skills/verify): the op's own time in a served program is the device
trace's. Refuses to run without a TPU; its last line is the JSON verdict."""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _timed(fn, carry, *rest, reps=20):
    import jax

    carry = jax.block_until_ready(fn(carry, *rest))
    t0 = time.perf_counter()
    for _ in range(reps):
        carry = fn(carry, *rest)
    jax.block_until_ready(carry)
    return (time.perf_counter() - t0) / reps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", type=int, default=128)
    ap.add_argument("--rows", default="128,2048")
    ap.add_argument("--hidden", type=int, default=2688)
    ap.add_argument("--width", type=int, default=1856)
    ap.add_argument("--experts", type=int, default=128)
    ap.add_argument("--top_k", type=int, default=6)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("hybrid_kernel_check: no TPU — a kernel's time has "
                         "no CPU mode")
    from benchmark.lib import moe_relu2_cost, peaks, ssm_cost
    from paddle_tpu.kernels.pallas.ssm_step import ssm_step
    from paddle_tpu.nn.layer import moe

    peak = peaks.peaks_for(jax.devices()[0].device_kind)
    out = {"device": jax.devices()[0].device_kind}
    ok = True

    # -- pt_ssm_step at [slots, 64, 64, 128], 8 heads a group ------------------
    R, H, P, N, G = args.slots, 64, 64, 128, 8
    k = jax.random.split(jax.random.PRNGKey(0), 7)
    state = jax.random.normal(k[0], (R, H, P, N), jnp.float32)
    x = jax.random.normal(k[1], (R, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[2], (R, H)))
    a = -jnp.exp(jax.random.normal(k[3], (H,)))
    b, c = (jax.random.normal(kk, (R, G, N)) for kk in k[4:6])
    d = jnp.ones((H,))
    with jax.default_matmul_precision("highest"):   # the kernel's float32
        want_s, want_y = ssm_step(state, x, dt, a, b, c, d, impl="reference")
    step = jax.jit(lambda s: ssm_step(s, x, dt, a, b, c, d, impl="pallas"),
                   donate_argnums=0)
    got_s, got_y = step(state + 0)
    errs = {"state": float(jnp.abs(got_s - want_s).max()),
            "y": float(jnp.abs(got_y - want_y).max()
                       / jnp.abs(want_y).max())}
    fine = errs["state"] < 1e-4 and errs["y"] < 1e-4
    ok = ok and fine
    took = _timed(lambda s: step(s)[0], got_s)
    floor = ssm_cost.floor_seconds(
        {"rows": R, "heads": H, "d_head": P, "d_state": N, "groups": G}, peak)
    out["ssm_step"] = {"shape": [R, H, P, N], "groups": G, "parity": fine,
                       "max_abs_err": errs, "ms_a_call": took * 1e3,
                       "floor_ms": floor["seconds"] * 1e3,
                       "of_floor_pct": 100 * floor["seconds"] / took}
    print("ssm_step " + json.dumps(out["ssm_step"]), flush=True)
    del state, got_s, want_s

    # -- the two-matrix grouped matmuls at width 1856 ---------------------------
    h, E, K = args.hidden, args.experts, args.top_k
    # as the model stores them: whole 128-lane tiles, zeros past the width
    # (``NemotronHConfig.expert_lanes``)
    w = -(-args.width // 128) * 128
    real = (jnp.arange(w) < args.width).astype(jnp.float32)
    k = jax.random.split(jax.random.PRNGKey(1), 5)
    wr = jax.random.normal(k[0], (h, E), jnp.float32) / np.sqrt(h)
    bias = 0.02 * jax.random.normal(k[1], (E,), jnp.float32)
    up = (jax.random.normal(k[2], (E, h, w), jnp.float32) * real
          / np.sqrt(h)).astype(jnp.bfloat16)
    down = (jax.random.normal(k[3], (E, w, h), jnp.float32) * real[:, None]
            / np.sqrt(args.width)).astype(jnp.bfloat16)
    kw = dict(top_k=K, first=0, score="sigmoid", norm_topk=True, scale=2.5)
    # (the stacked matrices are ARGUMENTS: 2.6 GB closed over would be
    # constants of the program)
    layer = jax.jit(lambda x, up, down: moe.moe_held_experts_mlp(
        x.astype(jnp.bfloat16), wr, None, up, down, x_route=x, bias=bias,
        **kw))

    @jax.jit
    def dense(x, up, down):
        """Every expert on every token (bfloat16 operands, float32 out of
        up, the square on the float32, bfloat16 into down), gated after."""
        gates, idx, _aux = moe._route(x, wr, K, score="sigmoid",
                                      norm_topk=True, scale=2.5,
                                      precision=jax.lax.Precision.HIGHEST,
                                      bias=bias)
        full = jnp.zeros((x.shape[0], E), jnp.float32).at[
            jnp.arange(x.shape[0])[:, None], idx].set(gates)
        xb = x.astype(jnp.bfloat16)

        def one(acc, e):
            u = jnp.matmul(xb, up[e], preferred_element_type=jnp.float32)
            y = jnp.matmul(jnp.square(jax.nn.relu(u)).astype(jnp.bfloat16),
                           down[e], preferred_element_type=jnp.float32)
            y = y.astype(jnp.bfloat16).astype(jnp.float32)
            return acc + full[:, e, None] * y, None

        return jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(E))[0]

    out["relu2_experts"] = {}
    for n in (int(r) for r in args.rows.split(",")):
        x = jax.random.normal(jax.random.fold_in(k[4], n), (n, h),
                              jnp.float32)
        got, stats = layer(x, up, down)
        want = dense(x, up, down)
        scale = float(jnp.abs(want).max())
        err = float(jnp.abs(got - want).max()) / scale
        # both sides round alike; what is left is the order of the float32
        # sums inside a tile
        fine = err < 2e-2 and int(stats["held"]) == n * K
        ok = ok and fine
        took = _timed(lambda _c, x: layer(x, up, down)[0], got, x)
        cost = moe_relu2_cost.gmm_cost(
            n * K, int(stats["experts_hit"]),
            {"hidden": h, "width": args.width, "itemsize": 2})
        floor = moe_relu2_cost.floor_seconds(cost, peak)
        out["relu2_experts"][n] = {
            "parity": fine, "max_err_over_max": err,
            "experts_hit": int(stats["experts_hit"]),
            "weight_streams": int(stats["weight_streams"]),
            "ms_a_layer": took * 1e3, "gmm_floor_ms": floor["seconds"] * 1e3,
            "floor_bound": floor["bound"]}
        print(f"relu2_experts rows={n} "
              + json.dumps(out["relu2_experts"][n]), flush=True)
    out["ok"] = ok
    print(json.dumps(out), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
