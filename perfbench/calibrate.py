"""Readings for the limits, on the chip at a cell's own size, in one process:
for each seed the reference against itself in the control's precision (fp8
operands) and with half of the batch left out, and with ``--witness`` in the
program's own (bfloat16 operands). No program and no window: the
reference stands in the program's place, as the contract allows for a
training cell. ``python3 -m perfbench.calibrate --workload <name> --seeds a,b,c``
prints one JSON line a seed and variant: every training number, and what
``check.verdict`` makes of them under the configuration's own ``limits``.
The control and each fault have to come out not correct and the witness
correct: the exit code is 1 where one does not. The sound runs' readings come
from the benchmark's own runs (``compared`` in each result line)."""

import argparse
import json
import os
import shutil
import sys

import numpy as np


def main(argv):
    p = argparse.ArgumentParser()
    p.add_argument('--workload', required=True)
    p.add_argument('--seeds', required=True)
    p.add_argument('--benchmark', default=None)
    p.add_argument('--rehearse', action='store_true')
    p.add_argument('--witness', action='store_true',
                   help='also the reference with bfloat16 operands')
    args = p.parse_args(argv)
    from perfbench import check, harness, lowprec
    files = harness.Files(args.benchmark or os.path.join(harness.ROOT,
                                                         'BENCHMARK.json'))
    cell = files.cell(args.workload)
    cfg, ref, _ = files.config(cell['config'])
    traffic = harness.load_json(files.find('traffic', cell['traffic'] + '.json'))
    kind_path = files.find('stores', cfg['store'] + '.py')
    import jax
    from jax.sharding import Mesh
    devices = jax.devices()
    if devices[0].platform != 'tpu' and not args.rehearse:
        print('no TPU', file=sys.stderr)
        return harness.NO_CHIP
    from petastorm_tpu.utils import enable_compile_cache
    enable_compile_cache()
    chips = cell['chips']
    mesh = Mesh(np.asarray(devices[:chips]), ('data',))
    batch = cfg['assumed']['rows_per_chip_per_step'] * chips
    control = lowprec.CONTROLS[cfg['compute_dtype']]
    # A store of three batches is enough (in whole row groups): the reference
    # needs rows, not epochs.
    per_group = cfg['assumed']['rows_per_row_group']
    small = dict(traffic, store_rows=-(-3 * batch // per_group) * per_group,
                 store_writers=min(traffic['store_writers'], 3))
    store_path = os.path.join(files.base, harness.WORK_DIR, 'stores',
                              'calibrate.' + cell['name'])
    unexpected = 0
    for seed in [int(s) for s in args.seeds.split(',')]:
        kind, url, _ = harness.build_store(kind_path, cfg, small, seed,
                                           store_path)
        expected = kind.Expected(url, cfg, seed, small['store_rows'])
        rng = np.random.default_rng([seed, 5])
        ids = rng.permutation(small['store_rows'])[:3 * batch]
        batches = [expected.rows(ids[i * batch:(i + 1) * batch])
                   for i in range(3)]
        truth = check.follow_reference(ref, cfg, seed, batches, mesh)
        variants = {'control_fp8': dict(quant=control),
                    'fault_half_batch': dict(rows_used=batch // 2)}
        if args.witness:
            # The reference at the program's own precision: a second witness
            # for what that precision alone does to a number.
            variants['witness_bf16'] = dict(quant=lowprec.BF16)
        if chips > 1:
            variants['fault_no_exchange'] = dict(rows_used=batch // chips)
        for name, kw in variants.items():
            if 'rows_used' in kw and chips > 1:
                # Rows that stand for the whole batch: repeat, so shapes and
                # sharding stay the cell's own.
                keep = kw['rows_used']
                faulty = [{k: np.concatenate([v[:keep]] * (batch // keep))
                           for k, v in b.items()} for b in batches]
                got = check.follow_reference(ref, cfg, seed, faulty, mesh)
            else:
                got = check.follow_reference(ref, cfg, seed, batches, mesh, **kw)
            numbers, where = check.training_numbers(got, truth)
            # No window here, so no rows to count: the training limits alone.
            table, correct = check.verdict(numbers, {
                k: v for k, v in cfg['limits'].items() if k in numbers})
            failed = sorted(k for k, (v, limit) in table.items()
                            if limit is not None and not v <= limit)
            unexpected += correct != name.startswith('witness')
            print(json.dumps(dict(numbers, seed=seed, variant=name,
                                  workload=args.workload, correct=correct,
                                  failed=failed, **where,
                                  losses=got['losses'],
                                  reference_losses=truth['losses'])),
                  flush=True)
    shutil.rmtree(store_path, ignore_errors=True)
    harness.say('{} of the readings came out other than they have to'.format(
        unexpected))
    return 1 if unexpected else 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
