"""Records the small trace that ``tests/data`` keeps: a few steps of a
jitted matrix product driven through the harness's own loop shape
(``next_batch``, ``dispatch_step``, ``await_step`` annotations, one step
kept in flight), with a host sleep in every other ``next_batch`` so that the
device idles where the reduction should say so. Prints the planes and lines
it finds, which is how ``trace_reduce.load_xplane`` was written against a
real trace. ``python3 -m perfbench.record_trace <out-dir>`` on a chip."""

import json
import os
import shutil
import sys
import time


def main(out_dir):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from perfbench import trace_reduce

    if jax.devices()[0].platform != 'tpu':
        print('no TPU', file=sys.stderr)
        return 3
    os.makedirs(out_dir, exist_ok=True)
    trace_dir = os.path.join(out_dir, 'trace')
    step = jax.jit(lambda a: jnp.tanh(a @ a) * 0.5)
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready(step(a))
    annotate = jax.profiler.TraceAnnotation
    jax.profiler.start_trace(trace_dir)
    pending = None
    for n in range(8):
        with annotate('next_batch'):
            if n % 2:
                time.sleep(0.004)
        with annotate('dispatch_step'):
            out = step(a)
        if pending is not None:
            with annotate('await_step'):
                jax.block_until_ready(pending)
        pending = out
    jax.block_until_ready(pending)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(trace_dir)
    shutil.copy(path, os.path.join(out_dir, 'small.xplane.pb'))
    data = ProfileData.from_file(path)
    for plane in data.planes:
        for line in plane.lines:
            events = list(line.events)
            print(plane.name, '|', line.name, '|', len(events), 'events',
                  sorted({e.name for e in events})[:12])
    trace = trace_reduce.load_xplane(path)
    reduced = trace_reduce.reduce_trace(trace)
    reduced.pop('per_op_s')
    print(json.dumps(reduced))
    with open(os.path.join(out_dir, 'small.events.json'), 'w') as f:
        json.dump(trace, f)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else 'chiprun_out/trace'))
