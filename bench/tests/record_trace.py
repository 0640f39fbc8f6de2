"""Record the small TPU trace that ``test_trace.py`` reads.

    python3 bench/tests/record_trace.py bench/tests/data/small.xplane.pb

On one chip: two jitted programs run three times each inside the same
``bench.*`` spans the harness uses, with a host sleep between calls, so the
trace holds device executions, idle gaps and the spans that cover them.
Prints the planes and lines it recorded.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 1

    def prefill_step(x):
        return jnp.tanh(x @ x).sum(axis=0)

    def decode_step(x):
        return (x * 2.0 + 1.0).sum()

    prefill, decode = jax.jit(prefill_step), jax.jit(decode_step)
    a = jnp.ones((2048, 2048), jnp.float32)
    prefill(a).block_until_ready()
    decode(a).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation(f"bench.prefill#{i}:2048"):
                y = prefill(a)
            y.block_until_ready()
            time.sleep(0.005)
            with jax.profiler.TraceAnnotation(f"bench.decode#{i}:8"):
                z = decode(a)
            z.block_until_ready()
            time.sleep(0.01)
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True))[-1]
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(path, out)
    shutil.rmtree(tmp, ignore_errors=True)
    data = jax.profiler.ProfileData.from_file(out)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for e in evs[:6]:
                print("     ", repr(e.name), e.start_ns, e.duration_ns,
                      [(k, str(v)[:60]) for k, v in list(e.stats)[:6]])
    print("SIZE", os.path.getsize(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
