#!/usr/bin/env python3
"""Record the small device trace the reduction is tested against.

Run once on a chip (``python benchmarks/chip/trace/record_fixture.py OUT``):
three iterations of a matmul, the flash kernels forward and backward and the
decode kernel at small shapes, with a host annotation around each iteration
and a deliberate host pause between them.  Writes ``OUT/fixture.xplane.pb``
and ``OUT/fixture.txt`` (planes, lines and the first events of each, for a
reader who wants to see how the trace is laid out).  Not part of a run.
"""

import glob
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))


def describe(path: str, per_line: int = 12) -> str:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(events)}")
            for e in events[:per_line]:
                stats = {k: (v if not isinstance(v, str) else v[:160])
                         for k, v in e.stats}
                out.append(f"    {e.name!r} start_ns={e.start_ns} "
                           f"dur_ns={e.duration_ns} stats={stats}")
    return "\n".join(out)


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.pallas import flash_attention
    from deepspeed_tpu.ops.pallas.decode_attention import cached_attention
    from deepspeed_tpu.utils.platform import require_tpu
    require_tpu()
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 1024, 2, 64), jnp.bfloat16)
    cache = jax.random.normal(key, (4, 1024, 2, 64), jnp.bfloat16)
    q1 = jax.random.normal(key, (4, 1, 2, 64), jnp.bfloat16)
    pos = jnp.asarray([100, 300, 700, 1000], jnp.int32)
    a = jax.random.normal(key, (1024, 1024), jnp.bfloat16)

    @jax.jit
    def step(q, cache, q1, pos, a):
        loss = lambda q: flash_attention(q, q, q, causal=True).astype(
            jnp.float32).sum()
        g = jax.grad(loss)(q)
        d = cached_attention(q1, cache, cache, pos)
        return g.sum() + d.astype(jnp.float32).sum() + (a @ a).sum()

    jax.block_until_ready(step(q, cache, q1, pos, a))
    trace_dir = os.path.join(out_dir, "_trace")
    jax.profiler.start_trace(trace_dir)
    for i in range(3):
        with jax.profiler.TraceAnnotation("fixture.iteration"):
            jax.block_until_ready(step(q, cache, q1, pos, a))
        with jax.profiler.TraceAnnotation("fixture.pause"):
            time.sleep(0.002)
    jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                "*.xplane.pb"))[0]
    shutil.copy(pb, os.path.join(out_dir, "fixture.xplane.pb"))
    shutil.rmtree(trace_dir)
    with open(os.path.join(out_dir, "fixture.txt"), "w") as f:
        f.write(describe(os.path.join(out_dir, "fixture.xplane.pb")))
    print("recorded", os.path.getsize(
        os.path.join(out_dir, "fixture.xplane.pb")), "bytes")


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
