"""SPMD scale-out sweep: the ``pallas_spmd`` backend across shard counts.

Runs one int8 SFC conv workload on 1/2/4/8-way meshes, sharding the batch
over 'data' or C_out over 'model', and appends per-shard-count rows to
``BENCH_conv.json`` (key ``"scaleout"``) next to the per-layer sweep from
``table3_throughput`` — the artifact CI uploads to track the perf
trajectory.

The sweep runs in this process on whatever devices JAX has, and refuses
with a message when there are fewer than two: it never starts a second
JAX process, which on a TPU host would contend for the chips this one
holds.  On a CPU host, give the process forced host devices before it
starts (CPU "devices" are host threads, so intra-host speedup is NOT the
point there; the rows track per-shard correctness — every row asserts
bit-identity against the single-device backend — and the shard_map
dispatch overhead).  On a multi-chip host the same sweep measures actual
scaling.

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m benchmarks.run scaleout
"""
import json
import os

import numpy as np

BENCH_PATH = os.environ.get("REPRO_BENCH_OUT", "BENCH_conv.json")


def _sweep(log) -> list:
    """Time the workload per (shards, axis); asserts single-device parity."""
    import jax
    import jax.numpy as jnp

    from repro.api import ConvSpec, get_backend, plan
    from repro.api.tuning import calibrate_act_scale, time_fn
    from repro.launch.mesh import make_forced_host_mesh
    from repro.quant import INT8_FREQ

    n = len(jax.devices())
    hw = int(os.environ.get("REPRO_BENCH_SPATIAL_CAP", "28"))
    reps = int(os.environ.get("REPRO_BENCH_REPS", "2"))
    B, cin, cout = 8, 64, 128
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, hw, hw, cin), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, cin, cout) * 0.1, jnp.float32)
    spec = ConvSpec.for_conv2d(x.shape, w.shape, quant=INT8_FREQ)

    def measure(p):
        act = calibrate_act_scale(x, p.algorithm, spec.quant)
        prep = p.prepare_weights(w, act_scale=act)
        y = p.apply(x, prep)
        dt = time_fn(jax.jit(lambda a: p.apply(a, prep)), x, reps=reps)
        return dt, y

    base_ms, y_ref = measure(plan(spec, backend="pallas", algo="sfc6_6"))
    base_ms *= 1e3
    rows = [{"shards": 1, "axis": None, "backend": "pallas",
             "ms": base_ms, "bit_identical": True}]
    log(f"scaleout shards=1 (single-device pallas): {base_ms:.2f}ms")

    backend = get_backend("pallas_spmd")
    try:
        for shards in (s for s in (1, 2, 4, 8) if s <= n):
            # shards=1 collapses both axes to the same (1, 1) mesh — one
            # row (the spmd dispatch overhead at 1 shard) is enough
            for axis in (("data",) if shards == 1 else ("data", "model")):
                shape = (shards, 1) if axis == "data" else (1, shards)
                backend.set_mesh(make_forced_host_mesh(shape))
                dt, y = measure(plan(spec, backend="pallas_spmd",
                                     algo="sfc6_6"))
                same = bool(jnp.all(y == y_ref))
                rows.append({"shards": shards, "axis": axis,
                             "backend": "pallas_spmd", "ms": dt * 1e3,
                             "bit_identical": same})
                log(f"scaleout shards={shards} axis={axis}: "
                    f"{dt*1e3:.2f}ms bit_identical={same}")
                assert same, f"SPMD output diverged at {shards}x{axis}"
    finally:
        backend.set_mesh(None)
    return rows


def run(log=print, bench_path: str = None) -> dict:
    import jax
    bench_path = bench_path or BENCH_PATH
    devices = jax.devices()
    if len(devices) < 2:
        raise SystemExit(
            f"scaleout needs at least 2 devices, JAX has {len(devices)} "
            f"({devices[0].platform}); on a CPU host start Python with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8")
    rows = _sweep(log)
    bench = {}
    if os.path.exists(bench_path):
        try:
            with open(bench_path) as f:
                bench = json.load(f)
        except ValueError:
            bench = {}
    bench["scaleout"] = {
        "workload": {"batch": 8, "cin": 64, "cout": 128, "algo": "sfc6_6",
                     "quant": "int8", "spatial_cap":
                     int(os.environ.get("REPRO_BENCH_SPATIAL_CAP", "28"))},
        "devices": len(devices),
        "platform": devices[0].platform,
        "rows": rows,
    }
    with open(bench_path, "w") as f:
        json.dump(bench, f, indent=1)
    log(f"bench_artifact,{bench_path}")
    return {"bench_path": bench_path, "rows": rows}


if __name__ == "__main__":
    run()
