"""Record the small profiler trace the trace-reduction test reads.

    python3 -m bench.tests.record_trace --out bench/tests/data/<name>

On a TPU: a few steps of a jitted step that runs the program's Pallas
``hash_decode`` kernel, a matmul and, where there are several chips, a
``psum`` over them, with idle gaps (host sleeps under named spans) between
steps, all inside a ``bench.window`` span.  Prints what the reduction reads
from it, which the test pins.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--steps", type=int, default=4)
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.kernels.hash_decode.kernel import hash_decode_fwd

    n = jax.device_count()
    interpret = jax.devices()[0].platform != "tpu"
    mesh = Mesh(jax.devices(), ("d",))
    codes = jax.random.randint(jax.random.key(0), (2048, 16), 0, 256)
    cb = jax.random.normal(jax.random.key(1), (16, 256, 512))

    def local(c, b):
        h = hash_decode_fwd(c, b, interpret=interpret)
        h = jnp.tanh(h @ b[0, :, :].T @ b[0])
        return jax.lax.psum(h.sum(), "d")

    step = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(P("d"), P()),
                                 out_specs=P(), check_vma=False))
    codes = jnp.tile(codes, (n, 1))
    step(codes, cb).block_until_ready()
    out = Path(args.out)
    shutil.rmtree(out, ignore_errors=True)
    with jax.profiler.trace(str(out)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(args.steps):
                with jax.profiler.TraceAnnotation("bench.host_wait"):
                    time.sleep(0.01)
                step(codes, cb).block_until_ready()
    from bench import trace as tr
    path = tr.newest_xplane(out)
    t = tr.load(path)
    win = t.window()
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(str(path)).planes:
        print(plane.name, [ln.name for ln in plane.lines][:12])
    print(json.dumps({
        "file": str(path.relative_to(out)), "bytes": path.stat().st_size,
        "devices": t.devices, "window_s": win[1] - win[0],
        "busy_s": [t.busy(d, win) for d in t.devices],
        "hash_decode_s": [t.op_time(d, win, "hash_decode") for d in t.devices],
        "collective_s": [t.op_time(d, win, tr.COLLECTIVE) for d in t.devices],
        "top_ops": t.top_ops(t.devices[0], win),
        "idle_gaps": t.idle_gaps(t.devices[0], win, 3),
        "op_names": sorted({nm for nm, _, _ in t.ops[t.devices[0]]})[:40],
        "host_lines": sorted({ln for ln, _, _, _ in t.host})[:20],
    }, indent=1))


if __name__ == "__main__":
    main()
