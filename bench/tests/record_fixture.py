"""Record the small trace that ``test_trace_reduce.py`` reads.

    python3 bench/tests/record_fixture.py bench/tests/data/tpu_events.json

Run on a TPU: inside a ``bench.window`` span it runs a jitted program
under a ``bench.busy`` span, sleeps under a ``bench.sleep`` span, runs
the program again, and then idles with no span open.  It writes the
events that :func:`trace_reduce.load_events` keeps, as JSON, with the
names of every plane and line for reference.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)


def main(out_path: str) -> int:
    import jax
    import jax.numpy as jnp

    import trace_reduce

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 3
    step = jax.jit(lambda a: jnp.tanh(a @ a))
    x = jnp.ones((2048, 2048), jnp.float32)
    step(x).block_until_ready()
    tmp = tempfile.mkdtemp(dir=os.path.dirname(os.path.abspath(out_path)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.busy"):
            step(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench.busy"):
            step(x).block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(tmp)
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = {p.name: sorted({ln.name for ln in p.lines}) for p in data.planes}
    events = [list(e) for e in trace_reduce.load_events(path)]
    shutil.rmtree(tmp, ignore_errors=True)
    with open(out_path, "w") as f:
        json.dump({"planes": planes, "events": events}, f)
    print(json.dumps(trace_reduce.reduce_events(
        [trace_reduce.Event(*e) for e in events])))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
