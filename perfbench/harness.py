"""One run of one cell: build the store and the weights from the seed, warm
the cell's shapes, measure for ``--seconds``, check, print the result line.

Driven by data: the cell, its configuration, its traffic mix and every
per-layer metric are files found by the names in ``BENCHMARK.json``
(``configs/<name>.json`` with ``.reference.py`` and ``.program.py`` beside
it, ``traffic/<name>.json``, ``stores/<kind>.py``, ``metrics/<name>.py``).
Nothing here names a cell.
"""

import argparse
import contextlib
import gc
import importlib.util
import json
import multiprocessing
import os
import resource
import shutil
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK_DIR = '.perfbench_work'        # in the checkout, git-ignored
TRACE_SECONDS = 4.0
NO_CHIP = 3


def say(*parts):
    print('[perfbench]', *parts, file=sys.stderr, flush=True)


def load_module(path):
    name = 'perfbench_file_' + os.path.basename(path).replace(
        '.', '_').replace('-', '_')
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path):
    with open(path) as f:
        return json.load(f)


class Files(object):
    """Finds a cell's files: under the benchmark's ``paths`` first, then
    beside this harness, so that a later benchmark adds files and edits none."""

    def __init__(self, benchmark_path):
        self.base = os.path.dirname(os.path.abspath(benchmark_path))
        self.benchmark = load_json(benchmark_path)
        self.dirs = [os.path.join(self.base, p)
                     for p in self.benchmark['paths']]
        if HERE not in self.dirs:
            self.dirs.append(HERE)

    def find(self, *parts):
        for d in self.dirs:
            path = os.path.join(d, *parts)
            if os.path.exists(path):
                return path
        raise FileNotFoundError('{} under none of {}'.format(
            os.path.join(*parts), self.dirs))

    def cell(self, name):
        for w in self.benchmark['workloads']:
            if w['name'] == name:
                return w
        raise SystemExit('no workload {!r} in BENCHMARK.json'.format(name))

    def config(self, name):
        for c in self.benchmark['configs']:
            if c['name'] == name:
                path = os.path.join(self.base, c['file'])
                cfg = load_json(path)
                stem = path[:-len('.json')]
                folder = os.path.dirname(path)
                ref = os.path.join(folder, cfg['reference_file']) \
                    if 'reference_file' in cfg else stem + '.reference.py'
                prog = os.path.join(folder, cfg['program_file']) \
                    if 'program_file' in cfg else stem + '.program.py'
                return cfg, load_module(ref), load_module(prog)
        raise SystemExit('no config {!r} in BENCHMARK.json'.format(name))

    def metrics(self, kind, cell_name, reported):
        """The readers of this cell's metrics of one kind, by name."""
        out = []
        for m in self.benchmark[kind]:
            if 'workloads' in m and cell_name not in m['workloads']:
                continue
            if kind == 'per_layer' and m['moves'] not in reported:
                continue
            out.append(m)
        return out


# -- the store ---------------------------------------------------------------------

def _write_part(job):
    kind_path, args = job
    return load_module(kind_path).write_part(args)


def build_store(kind_path, cfg, traffic, seed, path):
    """The store of this seed at ``path``, written anew by several processes
    in every run: set-up is then the same work whatever ran here before."""
    kind = load_module(kind_path)
    rows = traffic['store_rows']
    per_group = cfg['assumed']['rows_per_row_group']
    groups = rows // per_group
    url = 'file://' + path
    shutil.rmtree(path, ignore_errors=True)
    writers = max(1, min(traffic['store_writers'], groups,
                         (os.cpu_count() or 2) - 1))
    edges = [groups * i // writers for i in range(writers + 1)]
    jobs = [(kind_path, (url, cfg, seed, i, edges[i], edges[i + 1],
                         per_group)) for i in range(writers)]
    os.makedirs(path)
    if writers == 1:
        _write_part(jobs[0])
    else:
        pool = multiprocessing.get_context('spawn').Pool(writers)
        try:
            pool.map(_write_part, jobs)
        finally:
            pool.close()
            pool.join()         # every writer has ended before we go on
    kind.finalize(url, cfg)
    nbytes = sum(os.path.getsize(os.path.join(path, name))
                 for name in os.listdir(path))
    return kind, url, {'bytes': nbytes, 'rows': rows}


# -- counters at the window's two ends ----------------------------------------------

def _thread_ids(prefix):
    """Kernel ids of this process's live threads whose Python name starts
    with ``prefix`` (this interpreter does not hand names to the kernel)."""
    import threading
    return [t.native_id for t in threading.enumerate()
            if t.name.startswith(prefix) and t.native_id is not None]


def _thread_cpu_s(prefix):
    """CPU seconds of those threads."""
    ticks = os.sysconf('SC_CLK_TCK')
    total = 0.0
    for tid in _thread_ids(prefix):
        try:
            with open('/proc/self/task/{}/stat'.format(tid)) as f:
                fields = f.read().rsplit(')', 1)[1].split()
            total += (int(fields[11]) + int(fields[12])) / ticks
        except (OSError, IndexError, ValueError):
            continue                        # the thread ended meanwhile
    return total


def thread_placement(prefix):
    """Where a thread of this process (by name) last ran: cpu, the cpus it
    may use, and that cpu's NUMA node. For explaining an outlying run."""
    for tid in _thread_ids(prefix):
        try:
            with open('/proc/self/task/{}/stat'.format(tid)) as f:
                cpu = int(f.read().rsplit(')', 1)[1].split()[36])
            allowed = None
            with open('/proc/self/task/{}/status'.format(tid)) as f:
                for line in f:
                    if line.startswith('Cpus_allowed_list'):
                        allowed = line.split(':', 1)[1].strip()
            node = None
            base = '/sys/devices/system/node'
            for name in (os.listdir(base) if os.path.isdir(base) else ()):
                if name.startswith('node') and os.path.isdir(os.path.join(
                        base, name, 'cpu{}'.format(cpu))):
                    node = int(name[4:])
            return {'cpu': cpu, 'allowed': allowed, 'numa_node': node}
        except (OSError, IndexError, ValueError):
            continue
    return None


def _rss_mb():
    with open('/proc/self/status') as f:
        for line in f:
            if line.startswith('VmRSS:'):
                return int(line.split()[1]) / 1024.0
    return None


def snapshot(loader, reader):
    stats = loader.stats
    cache = getattr(reader, '_cache', None)
    timings = dict(stats.get('worker_stage_timings') or {})
    self_t = resource.getrusage(resource.RUSAGE_SELF)
    child_t = resource.getrusage(resource.RUSAGE_CHILDREN)
    keep = ('batches', 'wait_s', 'stage_dispatch_s', 'staged_bytes',
            'assemble_s', 'dispatch_s', 'reader_wait_s', 'arena_wait_s', 'arena_alloc',
            'arena_reuse', 'h2d_overlap_frac', 'overlap_frac', 'ready_wait_s',
            'shards_put', 'stage_tiers', 'n_devices')
    return {'t': time.perf_counter(),
            'stats': {k: stats[k] for k in keep if k in stats},
            'timings': timings,
            'cache_hits': getattr(cache, 'hits', 0),
            'cache_misses': getattr(cache, 'misses', timings.get('chunks', 0)),
            'cpu_s': (self_t.ru_utime + self_t.ru_stime
                      + child_t.ru_utime + child_t.ru_stime),
            'worker_cpu_s': _thread_cpu_s('pst-pool-worker'),
            'rss_mb': _rss_mb(),
            'rss_peak_mb': self_t.ru_maxrss / 1024.0}


# -- the run ---------------------------------------------------------------------------

def percentile(values, q):
    """The q-quantile by linear interpolation (numpy's default)."""
    values = sorted(values)
    pos = (len(values) - 1) * q
    lo = int(pos)
    hi = min(lo + 1, len(values) - 1)
    return values[lo] + (values[hi] - values[lo]) * (pos - lo)


def clear_environment():
    """Nothing outside the files steers a run."""
    for key in list(os.environ):
        if key.startswith(('PETASTORM_TPU_', 'BENCH_')):
            del os.environ[key]


def parse(argv):
    p = argparse.ArgumentParser(prog='perfbench.run')
    p.add_argument('--workload', required=True)
    p.add_argument('--seed', type=int, required=True)
    p.add_argument('--seconds', type=float, required=True)
    p.add_argument('--trace', type=int, choices=(0, 1), default=0)
    p.add_argument('--benchmark', default=os.path.join(ROOT, 'BENCHMARK.json'),
                   help='another BENCHMARK.json (tests)')
    p.add_argument('--rehearse', action='store_true',
                   help='allow a run without a TPU: kernels in interpret '
                        'mode, every metric renamed *.cpu_rehearsal')
    p.add_argument('--fault', default=None,
                   help='tests only: break the timed path (see faults.py)')
    return p.parse_args(argv)


def run(argv, process_start):
    args = parse(argv)
    clear_environment()
    files = Files(args.benchmark)
    cell = files.cell(args.workload)
    cfg, ref, program = files.config(cell['config'])
    traffic = load_json(files.find('traffic', cell['traffic'] + '.json'))
    kind_path = files.find('stores', cfg['store'] + '.py')
    e2e = files.metrics('end_to_end', cell['name'], None)
    e2e_names = {m['name'] for m in e2e}
    layer = files.metrics('per_layer', cell['name'], e2e_names)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != 'tpu' and not args.rehearse:
        say('no TPU: jax found {} device(s) of platform {!r}; a benchmark '
            'run never falls back'.format(len(devices), platform))
        return NO_CHIP
    if len(devices) < cell['chips']:
        say('the cell asks for {} chip(s), jax found {}'.format(
            cell['chips'], len(devices)))
        return NO_CHIP
    devices = devices[:cell['chips']]
    peaks = load_json(files.find('peaks.json'))
    if platform == 'tpu' and devices[0].device_kind not in peaks:
        raise SystemExit('device kind {!r} is not in peaks.json'.format(
            devices[0].device_kind))
    peak = peaks.get(devices[0].device_kind)

    from petastorm_tpu.utils import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', -1)

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from perfbench import check, faults, span_reduce, trace_reduce

    work = os.path.join(files.base, WORK_DIR)
    phases = {}
    t_phase = time.perf_counter()

    def phase(name):
        nonlocal t_phase
        now = time.perf_counter()
        phases[name] = round(now - t_phase, 3)
        t_phase = now

    phases['python_start'] = round(time.time() - process_start, 3)
    store_path = os.path.join(work, 'stores', cell['name'])
    kind, url, store_info = build_store(kind_path, cfg, traffic, args.seed,
                                        store_path)
    phase('store')

    chips = cell['chips']
    # A mesh on one chip too, as chip_smoke.py has it: every cell then stages
    # through the per-device streams, the path that exists across chips.
    mesh = Mesh(np.asarray(devices), ('data',))
    batch = cfg['assumed']['rows_per_chip_per_step'] * chips
    replicated = NamedSharding(mesh, PartitionSpec())
    params = jax.device_put(ref.init_params(cfg, args.seed), replicated)
    batch_stats = ref.init_batch_stats(cfg)
    if batch_stats is not None:
        batch_stats = jax.device_put(batch_stats, replicated)
    state, step = program.build(cfg, params, batch_stats, mesh,
                                interpret=platform != 'tpu')
    del params, batch_stats
    phase('weights')

    from petastorm_tpu import decode_budget, make_tensor_reader
    from petastorm_tpu.jax_loader import JaxLoader
    decode_budget.get_budget().set_total(traffic['decode_threads'])
    reader = make_tensor_reader(url, schema_fields=list(kind.FIELDS),
                                seed=args.seed % (2 ** 31 - 1),
                                **traffic['reader'])
    loader = JaxLoader(reader, batch, mesh=mesh, **traffic['loader'])
    shape, _ = kind.row_shape(cfg)[kind.CHECKED]
    checksum = check.make_device_checksum(kind.CHECKED, shape,
                                          'label' in kind.FIELDS)
    fault = faults.Fault(args.fault, batch, mesh)
    step = fault.wrap_step(step)
    spans = []              # (name, start_ns, duration_ns), host clock

    @contextlib.contextmanager
    def span(name):
        t0 = time.perf_counter_ns()
        yield
        spans.append((name, t0, time.perf_counter_ns() - t0))

    delivered = []          # (ids, sums) of every batch, left on the device
    program_numbers = {'losses': []}
    misplaced = 0
    t_first = time.perf_counter()
    first_batch_s = None
    # Set-up, part 1: the first three steps through the window's own feed and
    # call, one at a time, kept for the comparison with the reference.
    for n in range(3):
        b = fault.wrap_batch(next(loader))
        if first_batch_s is None:
            first_batch_s = time.perf_counter() - t_first
        delivered.append(checksum(b))
        misplaced += check.shards_misplaced(b, devices)
        state, metrics = step(state, b)
        program_numbers['losses'].append(float(metrics['loss']))
        if n == 0:
            tree, scale = program.first_gradient(state.opt_state, cfg)
            program_numbers['grad_norms'] = {
                name: scale * norm
                for name, norm in check.flat_norms(tree).items()}
            phase('compile_and_first_step')
    start = jax.device_put(ref.init_params(cfg, args.seed), replicated)
    program_numbers['update_norms'] = check.flat_norms(state.params,
                                                       minus=start)
    del start
    phase('check_steps')

    # Part 2: what the traffic needs (a RAM cache filled) and no more.
    fill = traffic.get('fill_cache_rows', 0)
    fill = traffic['store_rows'] if fill == 'store_rows' else int(fill)
    pulled = 3 * batch
    while pulled < fill:
        delivered.append(checksum(fault.wrap_batch(next(loader))))
        pulled += batch
    phase('cache_fill')

    # Part 3 and the window: one loop. Step k is dispatched, then step k-1 is
    # awaited, so the device always holds a step while the host works and a
    # completion is seen within a dispatch of when it happened.
    pending, done_t = None, []

    def one_step():
        nonlocal state, pending
        with span('next_batch'):
            b = fault.wrap_batch(next(loader))
        with span('dispatch_step'):
            delivered.append(checksum(b))
            state, metrics = step(state, b)
        if pending is not None:
            with span('await_step'):
                jax.block_until_ready(pending['loss'])
            done_t.append(time.perf_counter())
        pending = metrics

    for _ in range(traffic['warm_steps'] + 1):
        one_step()
    phase('warm_steps')
    del done_t[:-1]                 # the window opens at this completion
    del spans[:]
    loader.reset_stats()
    begin = snapshot(loader, reader)
    window_delivered_from = len(delivered) - 1
    setup_s = time.time() - process_start
    t_open = done_t[0]

    counted_seconds = args.seconds - (TRACE_SECONDS if args.trace else 0.0)
    counted_seconds = max(counted_seconds, 0.25 * args.seconds)
    while done_t[-1] - t_open < counted_seconds:
        one_step()
    end = snapshot(loader, reader)
    assemble_thread = thread_placement('pst-staging-assemble')
    intervals = [b - a for a, b in zip(done_t, done_t[1:])]
    steps = len(intervals)
    window_s = done_t[-1] - t_open
    rows = steps * batch
    rate = rows / window_s / chips

    # Rows completed a second in consecutive stretches of five seconds: says
    # whether a spread lies within a run or between runs.
    by_5s, edge, count = [], t_open, 0
    for t in done_t[1:]:
        count += 1
        if t - edge >= 5.0:
            by_5s.append(round(count * batch / (t - edge), 2))
            edge, count = t, 0

    # The window's three longest intervals with what the loop was in: an
    # outlying run is explained from its log.
    by_name = {name: [d / 1e6 for n, _, d in spans if n == name]
               for name in trace_reduce.HOST_SPANS}
    slowest = [{'interval_ms': 1e3 * intervals[i],
                'at_s': done_t[i + 1] - t_open,
                'next_batch_ms': by_name['next_batch'][i],
                'dispatch_step_ms': by_name['dispatch_step'][i],
                'await_step_ms': by_name['await_step'][i]}
               for i in sorted(range(steps), key=lambda i: -intervals[i])[:3]]

    reduced = None
    if args.trace:
        trace_dir = os.path.join(work, 'trace', 'run-{}'.format(os.getpid()))
        shutil.rmtree(trace_dir, ignore_errors=True)
        # Device planes only. With the host tracer on, the runtime's own
        # spans come too: the image batch's layout change on its way to the
        # chip is a million `Transpose` spans a batch, and writing them slows
        # that thread thirtyfold, so the traced steps starve (PERF.md). The
        # loop's spans are taken by the host's clock instead and pinned to
        # the trace's where the last step ends.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        del spans[:]
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        t_trace_ns = time.perf_counter_ns()
        t_trace = time.perf_counter()
        traced_steps = 0
        while time.perf_counter() - t_trace < TRACE_SECONDS or traced_steps < 5:
            one_step()
            traced_steps += 1
        jax.block_until_ready(pending['loss'])
        last_step_done_ns = time.perf_counter_ns()
        t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        say('trace: {} steps in {:.2f} s, then {:.2f} s to stop and write it'
            .format(traced_steps, t_stop - t_trace,
                    time.perf_counter() - t_stop))
        # The program's own spans of the traced window go onto the trace's
        # clock with the loop's, so that a gap names the pipeline stage too.
        # Where the program keeps no ring, or the ring has dropped part of
        # the window, there are none and the gaps keep the loop's names alone.
        stages = None
        ring = span_reduce.ring_records()
        ring = ring and span_reduce.clip(ring, t_trace_ns, last_step_done_ns)
        if ring and ring['covered']:
            stages = [(s[span_reduce.TID], s[span_reduce.NAME],
                       s[span_reduce.START], s[span_reduce.DUR])
                      for s in ring['spans']]
            say('trace: {} spans of the program on {} threads'.format(
                len(stages), len({s[0] for s in stages})))
        try:
            xplane = trace_reduce.find_xplane(trace_dir)
            trace = trace_reduce.load_xplane(xplane)
            trace['host'] = trace_reduce.spans_on_the_trace_clock(
                spans, last_step_done_ns, trace['devices'])
            if stages is not None:
                trace['stages'] = trace_reduce.stages_on_the_trace_clock(
                    stages, last_step_done_ns, trace['devices'])
            for plane, events in trace['devices'].items():
                say('trace: {} holds {} operations from {:.3f} s to {:.3f} s'
                    .format(plane, len(events),
                            min(e[1] for e in events) / 1e9,
                            max(e[1] + e[2] for e in events) / 1e9))
            for name, events in trace['host'].items():
                say('trace: {} spans of {}'.format(len(events), name))
            reduced = trace_reduce.reduce_trace(trace)
        except RuntimeError:
            if platform == 'tpu':
                raise
            say('rehearsal: a CPU trace has no device plane to reduce')
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    jax.block_until_ready(pending['loss'])

    memory = [d.memory_stats() or {} for d in devices]
    # What the allocator had to reserve at the worst moment: the programs'
    # scratch (activations) is in it, which 'peak_bytes_in_use' leaves out.
    memory_peak = max(max(m.get('peak_bytes_reserved', 0),
                          m.get('peak_bytes_in_use', 0)) for m in memory)
    final_stats = loader.stats
    loader.stop()
    reader.stop()
    reader.join()

    print(json.dumps({'window': {
        'rows_per_s_per_chip': rate, 'rows': rows, 'steps': steps,
        'batch': batch, 'seconds': window_s,
        'step_interval_ms': {'median': 1e3 * statistics.median(intervals),
                             'p95': 1e3 * percentile(intervals, 0.95),
                             'max': 1e3 * max(intervals)},
        'rows_per_s_by_5s': by_5s, 'slowest_steps': slowest,
        'reader_wait_share': (end['stats'].get('reader_wait_s', 0.0)
                              - begin['stats'].get('reader_wait_s', 0.0))
        / window_s,
        'setup_phases_s': phases, 'store': store_info,
        'first_batch_s': first_batch_s, 'memory_stats': memory[0],
        'counters': {'begin': begin, 'end': end}},
        'settings': {'workload': cell, 'traffic': traffic, 'seed': args.seed,
                     'trace': args.trace, 'compile_cache': cache_dir,
                     'cpus': sorted(os.sched_getaffinity(0)),
                     'assemble_thread': assemble_thread,
                     'stage_tiers': final_stats.get('stage_tiers'),
                     'arena_pinned': final_stats.get('arena_pinned'),
                     'arena_pinned_locked': final_stats.get('arena_pinned_locked')}}),
        flush=True)

    # The comparison, once the window has closed, the peak has been read and
    # the program's state is freed.
    pairs = [(np.asarray(i), np.asarray(s)) for i, s in delivered]
    check_ids = [p[0] for p in pairs[:3]]
    del delivered, state, pending, step, loader, reader
    gc.collect()
    t_check = time.perf_counter()
    expected = kind.Expected(url, cfg, args.seed, traffic['store_rows'])
    numbers = check.rows_numbers(pairs, expected, kind.CHECKED,
                                 traffic['store_rows'],
                                 traffic['sample_rows'], args.seed)
    compared = numbers.pop('rows_compared')
    numbers['shards_misplaced'] = misplaced
    check_rows = [expected.rows(ids) for ids in check_ids]
    shutil.rmtree(store_path, ignore_errors=True)   # a run leaves no store
    reference_numbers = check.follow_reference(ref, cfg, args.seed,
                                               check_rows, mesh)
    training, where = check.training_numbers(program_numbers,
                                             reference_numbers)
    numbers.update(training)
    table, correct = check.verdict(numbers, cfg['limits'])
    check_s = time.perf_counter() - t_check

    ctx = {'begin': begin, 'end': end, 'window_s': window_s, 'rows': rows,
           'steps': steps, 'batch': batch, 'chips': chips, 'cfg': cfg,
           'ref': ref, 'peak': peak, 'trace': reduced, 'rate': rate,
           'intervals': intervals, 'setup_s': setup_s,
           'first_batch_s': first_batch_s, 'memory_peak_bytes': memory_peak,
           'trace_reduce': trace_reduce, 'percentile': percentile}
    suffix = '' if platform == 'tpu' else '.cpu_rehearsal'
    out = {}
    for m in (layer if args.trace else e2e):
        value = load_module(files.find('metrics', m['name'] + '.py')).read(ctx)
        if value is not None:
            out[m['name'] + suffix] = {'value': value, 'unit': m['unit']}

    device = {'platform': platform, 'kind': devices[0].device_kind,
              'count': len(devices), 'memory_peak_bytes': memory_peak}
    result = {'correct': bool(correct), 'attempted': rows,
              'failed': int(numbers['rows_wrong']), 'metrics': out,
              'device': device}
    if reduced is not None:
        device['busy_s'] = reduced['busy_s']
        device['window_s'] = reduced['window_s']
        # The loop's entries (four at the most), then the program's stages
        # under the program's own names: ten in all, as the driver keeps.
        gaps = reduced['idle_gaps'] + (reduced['stage_gaps'] or [])
        result['breakdown'] = {'device_ops': reduced['device_ops'],
                               'idle_gaps': gaps[:10]}
    result['steps'] = steps
    result['check_s'] = check_s
    if args.rehearse:
        result['rehearsal'] = True
    result['compared'] = dict(table, rows_compared=[compared, None], **{
        k: [v, None] for k, v in where.items()})
    for name, (value, limit) in result['compared'].items():
        say('compared {} = {} (limit {})'.format(name, value, limit))
    say('correct = {}'.format(bool(correct)))
    print(json.dumps(result), flush=True)
    return 0
