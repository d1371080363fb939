"""What decides ``correct``: the timed path's first three steps against the
plain reference, and the rows the window delivered against an independent
read. Every number compared has a limit of its own, kept in the
configuration's json with the readings it was set from (``PERF.md``).

Training numbers, as a share of the reference's:

``loss_gap``     widest gap of a step's loss over the three steps.
``grad_gap``     worst leaf: gap between the norm of the first gradient as the
                 optimizer got it (from its state after step 1) and the
                 reference's, over the reference's norm of that leaf or of the
                 median leaf, whichever is larger.
``update_gap``   the same for the parameters' change after three steps, over
                 the leaves whose reference gradient is at least a thousandth
                 of the median leaf's (the others move by round-off alone).
``loss_gap_first``, ``grad_gap_median``, ``update_gap_median``: the first
                 step's loss alone, and the median leaf's gap in place of the
                 worst leaf's: steadier from seed to seed where one small
                 leaf's gradient is a sum that all but cancels.
``grad_gap_weights``, ``update_gap_weights``: the median leaf's gap over the
                 leaves of two or more dimensions, the weights that enter a
                 product: their gradient is a product's own result, where a
                 norm's scale or bias gets the sum of one over every position.
``grad_gap_weights_worst``, ``update_gap_weights_worst``: the worst leaf's gap
                 over those same leaves: a gradient or an update that is wrong
                 in a few of the weights (one left unmoved or moved double
                 reads 1) shows here and not in a median.

Rows (exact): ``rows_wrong`` device checksums that differ from the independent
read; ``rows_uneven`` how far a row's number of deliveries lies outside what
shuffled epochs allow; ``shards_misplaced`` fields of the check batches that
did not reach the chips as one shard each, in the mesh's order.
"""

import statistics

import numpy as np

CHECKSUM_MODULUS = 65521
ID_FACTOR = 2654435761
LABEL_FACTOR = 40503
ZERO_GRADIENT_SHARE = 1e-3


# -- checksums ------------------------------------------------------------------

def checksum_weights(shape):
    """Per-position weights: a checksum changes when any byte changes or two
    swap places."""
    count = int(np.prod(shape))
    return (np.arange(count, dtype=np.uint32) % CHECKSUM_MODULUS + 1).reshape(shape)


def host_checksum(rows, checked):
    """uint32 sums wrap modulo 2**32 on host and device alike, and modular
    sums do not depend on the order of reduction."""
    data = rows[checked]
    weights = checksum_weights(data.shape[1:])
    with np.errstate(over='ignore'):
        total = (data.astype(np.uint32) * weights).reshape(len(data), -1).sum(
            axis=1, dtype=np.uint32)
        total = total + rows['id'].astype(np.uint32) * np.uint32(ID_FACTOR)
        if 'label' in rows:
            total = total + rows['label'].astype(np.uint32) * np.uint32(LABEL_FACTOR)
    return total


def make_device_checksum(checked, shape, has_label):
    """``batch -> (ids, sums)``, both left on the device."""
    import jax
    import jax.numpy as jnp
    weights = jnp.asarray(checksum_weights(shape))

    @jax.jit
    def checksum(data, ids, label):
        total = jnp.sum((data.astype(jnp.uint32) * weights).reshape(
            data.shape[0], -1), axis=1, dtype=jnp.uint32)
        total = total + ids.astype(jnp.uint32) * jnp.uint32(ID_FACTOR)
        if label is not None:
            total = total + label.astype(jnp.uint32) * jnp.uint32(LABEL_FACTOR)
        return total

    return lambda batch: (batch.id, checksum(
        getattr(batch, checked), batch.id,
        batch.label if has_label else None))


def rows_numbers(delivered, expected, checked, store_rows, sample, seed):
    """``delivered``: list of (ids, sums) numpy pairs, one a batch, in order.
    Compares a sample of ids drawn from the seed, every copy of each, and
    counts how evenly the epochs delivered."""
    ids = np.concatenate([d[0] for d in delivered]).astype(np.int64)
    sums = np.concatenate([d[1] for d in delivered]).astype(np.uint32)
    seen = np.unique(ids)
    rng = np.random.default_rng([int(seed), 99])
    picked = seen if len(seen) <= sample else rng.choice(seen, sample,
                                                         replace=False)
    # The set-up's check batches come first: always in the sample.
    picked = np.union1d(picked, ids[:min(len(ids), 3 * len(delivered[0][0]))])
    want = dict(zip(picked.tolist(),
                    host_checksum(expected.rows(picked), checked).tolist()))
    mask = np.isin(ids, picked)
    wrong = sum(1 for i, s in zip(ids[mask].tolist(), sums[mask].tolist())
                if want[i] != s)
    unknown = int(np.sum((ids < 0) | (ids >= store_rows)))
    counts = np.bincount(ids[(ids >= 0) & (ids < store_rows)],
                         minlength=store_rows)
    # Shuffled epochs: any stretch of the stream holds a row between
    # floor(n / rows) - 1 and ceil(n / rows) + 1 times.
    lo = max(0, len(ids) // store_rows - 1)
    hi = -(-len(ids) // store_rows) + 1
    uneven = int(max(0, counts.max() - hi) + max(0, lo - counts.min()))
    return {'rows_wrong': wrong + unknown, 'rows_uneven': uneven,
            'rows_compared': int(mask.sum())}


def shards_misplaced(batch, devices):
    """Fields of a staged batch that are not one shard of equal rows on each
    chip, in the mesh's order."""
    bad = 0
    for array in batch:
        shards = sorted(array.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        rows = array.shape[0] // len(devices)
        if [s.device for s in shards] != list(devices) or any(
                s.data.shape[0] != rows for s in shards):
            bad += 1
    return bad


# -- training numbers --------------------------------------------------------------

def _names(tree):
    import jax
    return ['/'.join(str(getattr(p, 'key', getattr(p, 'name', p)))
                     for p in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def flat_norms(tree, minus=None):
    """path string -> float norm of every leaf (of ``tree - minus`` where
    that is given), from one jitted reduction: never leaf by leaf."""
    import jax
    import jax.numpy as jnp

    def norms(leaves, others):
        if others is not None:
            leaves = [a - b for a, b in zip(leaves, others)]
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
            leaf.astype(jnp.float32)))) for leaf in leaves])

    leaves = jax.tree_util.tree_leaves(tree)
    others = None if minus is None else jax.tree_util.tree_leaves(minus)
    values = np.asarray(jax.jit(norms)(leaves, others))
    return dict(zip(_names(tree), values.tolist()))


def leaf_gaps(program, reference, keep=None):
    """For every leaf the gap of norms over the reference's norm of that leaf
    or of the median leaf, whichever is larger. Returns the worst gap, the
    median gap and the worst leaf's name. ``keep`` narrows the leaves whose
    gaps count, and the median leaf is the median of those."""
    names = [n for n in reference if keep is None or n in keep]
    floor = statistics.median(reference[n] for n in names)
    gaps = {n: abs(program[n] - reference[n]) / max(reference[n], floor)
            for n in names}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], statistics.median(gaps.values()), worst


def training_numbers(program, reference):
    """``program`` / ``reference``: {'losses': [3], 'grad_norms': {},
    'update_norms': {}}, the reference with ``weights`` too, the names of its
    leaves of two or more dimensions. A configuration's ``limits`` say which
    of these numbers it compares; the others are printed beside them."""
    loss_gaps = [abs(p - r) / abs(r)
                 for p, r in zip(program['losses'], reference['losses'])]
    grad_gap, grad_median, grad_at = leaf_gaps(program['grad_norms'],
                                               reference['grad_norms'])
    floor = ZERO_GRADIENT_SHARE * statistics.median(
        reference['grad_norms'].values())
    moved = {n for n, v in reference['grad_norms'].items() if v >= floor}
    update_gap, update_median, update_at = leaf_gaps(
        program['update_norms'], reference['update_norms'], moved)
    weights = set(reference['weights'])
    grad_weights, grad_weights_median, grad_weights_at = leaf_gaps(
        program['grad_norms'], reference['grad_norms'], weights)
    update_weights, update_weights_median, update_weights_at = leaf_gaps(
        program['update_norms'], reference['update_norms'], weights & moved)
    return ({'loss_gap': max(loss_gaps), 'loss_gap_first': loss_gaps[0],
             'grad_gap': grad_gap, 'grad_gap_median': grad_median,
             'grad_gap_weights': grad_weights_median,
             'grad_gap_weights_worst': grad_weights,
             'update_gap': update_gap, 'update_gap_median': update_median,
             'update_gap_weights': update_weights_median,
             'update_gap_weights_worst': update_weights},
            {'grad_gap_leaf': grad_at, 'update_gap_leaf': update_at,
             'grad_gap_weights_leaf': grad_weights_at,
             'update_gap_weights_leaf': update_weights_at,
             'leaves_left_out': len(reference['grad_norms']) - len(moved)})


def follow_reference(ref, cfg, seed, batches, mesh=None, quant=None,
                     rows_used=None):
    """The reference through the first three steps on the rows of the
    independent read: losses, first-gradient norms, update norms."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    params = ref.init_params(cfg, seed)
    if mesh is not None:
        params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
    start = params
    opt = ref.opt_init(params, cfg)
    out = {'losses': [], 'weights': [
        name for name, leaf in zip(flat_norms(params), jax.tree_util.tree_leaves(
            params)) if leaf.ndim >= 2]}
    for n, rows in enumerate(batches):
        inputs = {k: v for k, v in rows.items() if k in cfg['input_fields']}
        if mesh is not None:
            inputs = jax.device_put(inputs, NamedSharding(
                mesh, PartitionSpec(mesh.axis_names[0])))
        loss, grads = ref.loss_and_grad(params, inputs, cfg, quant=quant,
                                        rows_used=rows_used)
        out['losses'].append(float(loss))
        if n == 0:
            out['grad_norms'] = flat_norms(
                ref.gradient_as_optimizer_gets_it(grads, params, cfg))
        params, opt = ref.opt_apply(params, opt, grads, cfg, n + 1)
        del grads
    out['update_norms'] = flat_norms(params, minus=start)
    return out


def verdict(numbers, limits):
    """``{name: [value, limit]}`` and whether every value keeps its limit. A
    number the configuration sets no limit for is shown with ``None`` and not
    compared; a value that is not a number fails."""
    table, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        table[name] = [value, limit]
        if limit is not None and not (value == value and value <= limit):
            ok = False
    missing = set(limits) - set(numbers)
    if missing:
        raise KeyError('limits for numbers nobody computes: {}'.format(
            sorted(missing)))
    return table, ok
