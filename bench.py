#!/usr/bin/env python
"""Throughput benchmark. Prints ONE JSON line.

Two workloads:

1. **hello_world** — parity with the reference's benchmark tutorial
   (``docs/benchmarks_tutorial.rst:20-21`` -> 709.84 samples/sec; harness
   ``petastorm/benchmark/throughput.py``): same schema (id + 128x256x3 png +
   4-D uint8 ndarray, ``examples/hello_world/.../generate_petastorm_dataset.py:29-62``),
   measured as decoded-samples/sec through a thread pool.

2. **imagenet (north star)** — BASELINE.json's target workload: 224x224 jpeg
   ``CompressedImageCodec`` rows read via ``make_tensor_reader`` (decoded-
   columnar worker, C++ batch decode into contiguous blocks, decoded-chunk
   RAM cache) -> ``JaxLoader`` block fast path -> a jitted ResNet-50 train
   step on the TPU, reporting ``img/s/chip``, ``input_stall_frac`` and a
   per-stage profile (target: >=2000 img/s/chip, <5% stall).

Every measurement that touches JAX runs in a *subprocess child*, one after
another: the parent never imports jax, so each child is the one process
that holds the chip. ``python bench.py`` needs a TPU and exits non-zero
without one; a child that fails fails the run. The children still run on the
CPU when ``JAX_PLATFORMS=cpu`` is given explicitly (the tests do that), and
every child's JSON names the ``platform`` it ran on.
"""

import json
import os
import statistics
import subprocess
import sys
import tempfile
import time

import numpy as np

_BASELINE_SAMPLES_PER_SEC = 709.84   # reference docs/benchmarks_tutorial.rst:20-21
_NORTH_STAR_IMG_PER_SEC = 2000.0     # BASELINE.json: >=2000 img/s/chip
_ROWS = 400
_IMAGENET_ROWS = 2048
_IMAGENET_ROWS_PER_GROUP = 256
# Parameterized dirs: changing the generation parameters invalidates the
# cached dataset instead of silently measuring a stale-shape store.
_DATASET_DIR = '/tmp/petastorm_tpu_bench_dataset_r{}'.format(_ROWS)
_IMAGENET_DIR = '/tmp/petastorm_tpu_bench_imagenet_r{}_g{}'.format(
    _IMAGENET_ROWS, _IMAGENET_ROWS_PER_GROUP)
_IMAGE_SIZE = 224
_LOOKUP_ROWS = 512                   # lookup child: unique-keyed store
_LOOKUP_ROWS_PER_GROUP = 64
_LM_ROWS = 2048
_LM_SEQ = 1025                       # 1024 inputs + shifted next-token targets
_WARMUP_SAMPLES = 200
_MEASURE_SAMPLES = 2000


def _repo_on_path():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------------
# dataset generation (CPU-only; runs in the parent so child timeouts cover
# only JAX work)
# --------------------------------------------------------------------------

def _ensure_hello_dataset():
    from petastorm_tpu.codecs import CompressedImageCodec, NdarrayCodec, ScalarCodec
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.unischema import Unischema, UnischemaField

    marker = os.path.join(_DATASET_DIR, '_common_metadata')
    if os.path.exists(marker):
        return 'file://' + _DATASET_DIR

    schema = Unischema('HelloWorldSchema', [
        UnischemaField('id', np.int32, (), ScalarCodec(np.int32), False),
        UnischemaField('image1', np.uint8, (128, 256, 3), CompressedImageCodec('png'), False),
        UnischemaField('array_4d', np.uint8, (None, 128, 30, None), NdarrayCodec(), False),
    ])
    rng = np.random.default_rng(0)

    def rows():
        for i in range(_ROWS):
            yield {'id': i,
                   'image1': rng.integers(0, 255, (128, 256, 3), dtype=np.uint8),
                   'array_4d': rng.integers(0, 255, (4, 128, 30, 3), dtype=np.uint8)}

    write_dataset('file://' + _DATASET_DIR, schema, rows(), rows_per_row_group=32)
    return 'file://' + _DATASET_DIR


def _synthetic_image(rng, size):
    """Natural-image-ish synthetic photo: low-frequency random field upsampled
    plus mild noise — compresses/decodes like a photo, unlike white noise."""
    low = rng.integers(0, 255, (size // 16, size // 16, 3), dtype=np.uint8)
    img = np.kron(low, np.ones((16, 16, 1), dtype=np.uint8))
    noise = rng.integers(0, 24, (size, size, 3), dtype=np.uint8)
    return np.clip(img.astype(np.int16) + noise - 12, 0, 255).astype(np.uint8)


def _ensure_imagenet_dataset():
    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.unischema import Unischema, UnischemaField

    marker = os.path.join(_IMAGENET_DIR, '_common_metadata')
    if os.path.exists(marker):
        return 'file://' + _IMAGENET_DIR

    # ImageNet-shaped: fixed 224x224 jpeg + integer label (reference
    # examples/imagenet/schema.py role; fixed size so the bench isolates
    # decode+stage+train, not resize policy).
    schema = Unischema('ImagenetBenchSchema', [
        UnischemaField('image', np.uint8, (_IMAGE_SIZE, _IMAGE_SIZE, 3),
                       CompressedImageCodec('jpeg', 90), False),
        UnischemaField('label', np.int64, (), ScalarCodec(np.int64), False),
    ])
    rng = np.random.default_rng(7)

    def rows():
        for i in range(_IMAGENET_ROWS):
            yield {'image': _synthetic_image(rng, _IMAGE_SIZE),
                   'label': int(rng.integers(0, 1000))}

    # 256-row groups: a 128-batch then lies inside one decoded chunk, so the
    # loader's block fast path slices views instead of concatenating.
    write_dataset('file://' + _IMAGENET_DIR, schema, rows(),
                  rows_per_row_group=_IMAGENET_ROWS_PER_GROUP)
    return 'file://' + _IMAGENET_DIR


def _ensure_lm_dataset(vocab, seq=_LM_SEQ):
    from petastorm_tpu.codecs import NdarrayCodec
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.unischema import Unischema, UnischemaField

    # Every generation parameter in the dir name: a toy-vocab CI run (or a
    # long-context sweep) must not leave a store another config would
    # silently reuse.
    n_rows = _LM_ROWS if seq <= 2048 else max(256, _LM_ROWS * 1024 // seq)
    lm_dir = '/tmp/petastorm_tpu_bench_lm_r{}_t{}_v{}'.format(
        n_rows, seq, vocab)
    marker = os.path.join(lm_dir, '_common_metadata')
    if os.path.exists(marker):
        return 'file://' + lm_dir

    # Token sequences as fixed-shape int32 rows: the long-context flagship's
    # input through the SAME Parquet -> tensor-reader path as images.
    schema = Unischema('LMBenchSchema', [
        UnischemaField('tokens', np.int32, (seq,), NdarrayCodec(), False),
    ])
    rng = np.random.default_rng(11)

    def rows():
        for _ in range(n_rows):
            yield {'tokens': rng.integers(0, vocab, seq, dtype=np.int32)}

    write_dataset('file://' + lm_dir, schema, rows(), rows_per_row_group=256)
    return 'file://' + lm_dir


def _child_lm(workers):
    """Third model family on real data: decoder-only TransformerLM (flash
    attention) trained from a token Parquet store through
    make_tensor_reader -> JaxLoader, lax.scan-amortized steps; reports
    tokens/s/chip + analytic MFU. Token batches are tiny (~4 KB/row), so
    this measures the model step, fed by the real pipeline."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import optax

    from petastorm_tpu import make_tensor_reader
    from petastorm_tpu.jax_loader import JaxLoader
    from petastorm_tpu.models import TransformerLM
    from petastorm_tpu.parallel import make_mesh
    from petastorm_tpu.utils import enable_compile_cache

    enable_compile_cache()
    platform = jax.devices()[0].platform
    n_devices = jax.device_count()
    # Multi-device hosts get a data mesh so the per-chip division below is
    # honest (same rule as _child_imagenet): tokens shard over 'data',
    # params replicate, and GSPMD inserts the gradient all-reduce.
    mesh = make_mesh({'data': n_devices}) if n_devices > 1 else None
    # ~42M params at the defaults (16.8M embed + 16.8M head + 8 x 3.1M
    # blocks); env overrides let CI smoke the path with a toy config.
    vocab = int(os.environ.get('BENCH_LM_VOCAB', '32768'))
    d_model = int(os.environ.get('BENCH_LM_DMODEL', '512'))
    n_layers = int(os.environ.get('BENCH_LM_LAYERS', '8'))
    n_heads = int(os.environ.get('BENCH_LM_HEADS', '8'))
    batch = int(os.environ.get('BENCH_LM_BATCH', '8')) * n_devices
    scan_k = max(1, int(os.environ.get('BENCH_LM_SCAN_K', '8')))
    measure_iters = max(1, int(os.environ.get('BENCH_LM_STEPS', '48')) // scan_k)
    seq = int(os.environ.get('BENCH_LM_SEQ', str(_LM_SEQ)))
    t = seq - 1
    # >0: Switch MoE MLPs (top-1 routing). NOT the dense FLOP basis: the
    # dense-dispatch einsums and the capacity padding are real retired
    # FLOPs, accounted below so lm_mfu stays honest across variants.
    moe = int(os.environ.get('BENCH_LM_MOE', '0'))

    url = _ensure_lm_dataset(vocab, seq)
    model = TransformerLM(vocab_size=vocab, d_model=d_model,
                          num_heads=n_heads, num_layers=n_layers, max_len=t,
                          moe_experts=moe,
                          # The same Pallas kernel everywhere: compiled on
                          # the chip; an explicit CPU run names the
                          # interpreter in lm_config.attention.
                          attention=('flash' if platform == 'tpu'
                                     else 'flash:interpret'))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, t), jnp.int32))
    tx = optax.sgd(0.01, momentum=0.9)
    opt_state = tx.init(params)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        replicate = NamedSharding(mesh, PartitionSpec())
        params, opt_state = jax.device_put((params, opt_state), replicate)

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_scan(params, opt_state, tokens_k):     # [K, B, T+1]
        def body(carry, tokens):
            params, opt_state = carry
            x, y = tokens[:, :-1], tokens[:, 1:]

            def loss_fn(p):
                if moe:
                    # Switch load-balance loss (models/moe.py:14-16): without
                    # it top-1 routing collapses onto few experts and the
                    # bench would measure a degenerate configuration.
                    logits, mods = model.apply(p, x,
                                               mutable=['intermediates'])
                    aux = sum(jax.tree_util.tree_leaves(
                        mods['intermediates']))
                    ce = optax.softmax_cross_entropy_with_integer_labels(
                        logits, y).mean()
                    return ce + 1e-2 * aux
                logits = model.apply(p, x)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, y).mean()

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state), loss

        (params, opt_state), losses = jax.lax.scan(body, (params, opt_state),
                                                   tokens_k)
        return params, opt_state, losses

    reader = make_tensor_reader(url, schema_fields=['tokens'],
                                reader_pool_type='thread',
                                workers_count=workers, num_epochs=None,
                                shuffle_row_groups=True, seed=0,
                                cache_type='memory')
    with reader:
        with JaxLoader(reader, batch * scan_k, mesh=mesh,
                       last_batch='drop') as loader:
            it = iter(loader)

            def group():
                sb = next(it)
                return sb.tokens.reshape(scan_k, batch, seq)

            for _ in range(2):                        # compile + warm cache
                params, opt_state, losses = train_scan(params, opt_state,
                                                       group())
            jax.block_until_ready(losses)
            loader.reset_stats()
            t0 = time.perf_counter()
            for _ in range(measure_iters):
                params, opt_state, losses = train_scan(params, opt_state,
                                                       group())
            jax.block_until_ready(losses)
            elapsed = time.perf_counter() - t0
            final_loss = float(losses[-1])
            stats = loader.stats
    steps = measure_iters * scan_k
    tok_rate = batch * t * steps / elapsed
    # Analytic fwd FLOPs/token: per layer 2*(4d^2 + T*d + mlp) MACs->FLOPs —
    # qkvo 4d^2 + TWO causal-average attention matmuls (QK^T and AV at T/2
    # each) + the MLP — plus the vocab head. Dense MLP: 8d^2. Switch MoE
    # (models/moe.py): expert matmuls run E*C slots per T tokens (capacity
    # padding) and the dense-dispatch/combine einsums cost E*C*d each —
    # all real retired FLOPs, so the MoE basis must include them.
    if moe:
        capacity = max(1, int(-(-t * 1.25 // moe)))
        mlp_macs = (8 * d_model * d_model * moe * capacity // t
                    + 2 * moe * capacity * d_model)
    else:
        mlp_macs = 8 * d_model * d_model
    fwd_flops_token = 2 * (n_layers * (4 * d_model * d_model + t * d_model
                                       + mlp_macs)
                           + d_model * vocab)
    # No chip peak to normalize against on an explicit CPU run.
    mfu = (None if platform == 'cpu' else
           _mfu(fwd_flops_token, tok_rate / n_devices,
                _peak_bf16_flops(jax.devices()[0])))
    print(json.dumps({
        'lm_tokens_per_sec_per_chip': round(tok_rate / n_devices, 1),
        'lm_step_time_ms': round(1000 * elapsed / steps, 2),
        'lm_final_loss': round(final_loss, 4),
        'lm_input_stall_frac': stats['input_stall_frac'],
        'lm_mfu': mfu,
        'platform': platform,
        'n_devices': n_devices,
        'lm_config': {'vocab': vocab, 'd_model': d_model,
                      'layers': n_layers, 'heads': n_heads, 'seq': t,
                      'batch_per_chip': batch // n_devices,
                      'scan_microbatches': scan_k, 'steps': steps,
                      'attention': model.attention, 'moe_experts': moe,
                      'fwd_flops_per_token': fwd_flops_token},
    }))


# --------------------------------------------------------------------------
# host-CPU reader throughput (the reference's benchmark quantity)
# --------------------------------------------------------------------------

def _measure_reader(url, workers, cache_type='null', pool='thread'):
    from petastorm_tpu import make_reader

    with make_reader(url, reader_pool_type=pool, workers_count=workers,
                     num_epochs=None, shuffle_row_groups=True, seed=0,
                     cache_type=cache_type) as reader:
        for _ in range(_WARMUP_SAMPLES):
            next(reader)
        start = time.perf_counter()
        for _ in range(_MEASURE_SAMPLES):
            next(reader)
        elapsed = time.perf_counter() - start
    return _MEASURE_SAMPLES / elapsed


# --------------------------------------------------------------------------
# jax children (each prints ONE json line; parent runs them with a timeout)
# --------------------------------------------------------------------------

def _child_staging(url, workers, pool='thread'):
    """hello_world batches staged to the default JAX device."""
    import jax

    from petastorm_tpu import make_reader
    from petastorm_tpu.jax_loader import JaxLoader, PadTo
    from petastorm_tpu.utils import enable_compile_cache

    enable_compile_cache()

    batch = 32
    n_batches = 40
    with make_reader(url, reader_pool_type=pool, workers_count=workers,
                     num_epochs=None, shuffle_row_groups=True, seed=0) as reader:
        with JaxLoader(reader, batch,
                       shape_policies={'array_4d': PadTo((4, 128, 30, 3))}) as loader:
            first = next(loader)
            jax.block_until_ready(first.image1)
            loader.reset_stats()
            start = time.perf_counter()
            got = 0
            for b in loader:
                jax.block_until_ready(b.image1)
                got += 1
                if got >= n_batches:
                    break
            elapsed = time.perf_counter() - start
            stall = loader.stats.get('input_stall_frac')
    print(json.dumps({'jax_staged_samples_per_sec': round(batch * got / elapsed, 2),
                      'hello_input_stall_frac': stall,
                      'platform': jax.devices()[0].platform}))


def _robustness_counters(stats):
    """Retry / quarantine / worker-respawn counters for a stage profile.

    Regressions here (retries climbing, workers dying, row-groups getting
    quarantined) are pipeline-health problems that raw throughput hides —
    BENCH_*.json carries them so they diff across rounds. Retry counts are
    consumer-process-local (worker-process retries are invisible here);
    respawns and quarantines come from the reader's diagnostics.
    """
    from petastorm_tpu.retry import retry_counters

    reader_diag = stats.get('reader_diagnostics') or {}
    return {
        'retries': sum(retry_counters().values()),
        'worker_respawns': reader_diag.get('worker_respawns', 0),
        'quarantined_rowgroups': len(reader_diag.get('quarantined_rowgroups') or ()),
    }


def _metrics_snapshot():
    """Full metrics-registry snapshot (petastorm_tpu.metrics) for a stage
    profile: BENCH_r0N files then carry every registered counter —
    staging, autotune, watchdog, chunk store, retries/respawns — not the
    hand-picked subsets above, so a new instrument shows up in bench
    diffs with zero bench changes. JSON-safe by the collect() contract."""
    try:
        from petastorm_tpu import metrics
        return metrics.get_registry().collect()
    except Exception as e:  # noqa: BLE001 - telemetry must not sink a bench
        return {'error': repr(e)}


def _lineage_summary(loader, ledger_dir):
    """Provenance-ledger block for a stage profile (ISSUE 7): records
    emitted vs dropped, write-behind lag, ledger bytes on disk, and a
    replay self-check — the newest ring record re-materialized from the
    dataset and digest-verified bit-identical (True / 'failed: ...').
    Removes the child's throwaway ledger dir afterwards."""
    import shutil

    tracker = getattr(loader, 'lineage_tracker', None)
    if tracker is None:
        return None
    out = dict(tracker.stats())
    path = out.pop('ledger_path', None)
    try:
        out['ledger_bytes'] = os.path.getsize(path) if path else 0
    except OSError:
        out['ledger_bytes'] = None
    ring = tracker.ring()
    check = None
    if ring:
        from petastorm_tpu import lineage as lineage_mod
        try:
            lineage_mod.verify_record(ring[-1], tracker.ctx)
            check = True
        except Exception as e:  # noqa: BLE001 - the bench must report, not die
            check = 'failed: {!r}'.format(e)
    out['replay_self_check'] = check
    shutil.rmtree(ledger_dir, ignore_errors=True)
    return out


def _staging_counters(stats):
    """Staging-engine health for a stage profile (ISSUE 2): per-stage busy
    seconds, assemble/dispatch co-activity (``overlap_frac`` — 0.0 was the
    PROFILE_r05 finding this engine exists to fix), and arena recycling
    (``arena_alloc`` must stay near zero after warmup while ``arena_reuse``
    climbs; ``arena_wait_s`` is assembler backpressure)."""
    out = {k: stats.get(k, 0) for k in
           ('assemble_s', 'dispatch_s', 'overlap_s', 'overlap_frac',
            'overlap_frac_total', 'ready_wait_s', 'arena_reuse',
            'arena_alloc', 'arena_wait_s')}
    # Per-device dispatch engaged: pass the stager's host/H2D co-activity
    # through so the profile reports the STREAMED path's overlap (the
    # one-shot _measure_h2d probe cannot see it and used to claim 0.0).
    for k in ('h2d_overlap_frac', 'h2d_overlap', 'n_devices', 'shards_put',
              'arena_pinned', 'arena_pinned_bytes'):
        if k in stats:
            out[k] = stats[k]
    return out


def _autotune_summary(stats):
    """Compact autotune record for a bench JSON: current knob values, the
    decision log tail, and the knob trajectory (ISSUE 4: the children run
    with the controller on and must emit what it did)."""
    at = stats.get('autotune')
    if not at:
        return None
    return {'knobs': at.get('knobs'),
            'last_class': at.get('last_class'),
            'ticks': at.get('ticks'),
            'paused_ticks': at.get('paused_ticks'),
            'reverts': at.get('reverts'),
            'decisions': at.get('decisions', [])[-40:],
            'trajectory': at.get('trajectory', [])[-40:]}


def _rss_mb():
    """Current resident-set size in MB (statm; peak-RSS fallback)."""
    try:
        with open('/proc/self/statm') as f:
            pages = int(f.read().split()[1])
        return round(pages * os.sysconf('SC_PAGE_SIZE') / 1e6, 1)
    except (OSError, ValueError, IndexError):  # pragma: no cover - non-linux
        return _peak_rss_mb()


def _peak_rss_mb():
    """Lifetime PEAK resident-set size in MB (``ru_maxrss``): the number a
    memory-regression gate wants — the current RSS at sample time misses
    every transient high-water mark between samples. The Linux-KB vs
    macOS-bytes quirk lives in one place (membudget)."""
    from petastorm_tpu import membudget
    # Decimal MB to match _rss_mb in the same record (binary MB would
    # read ~4.9% low next to it — peak must never print below current).
    return round(membudget.peak_rss_bytes() / 1e6, 1)


def _mem_governor_summary():
    """Compact memory-governor block for a stage profile, or None while
    unarmed: budget + provenance, ladder peaks, per-action degrade counts,
    breaches. Future BENCH rounds gate host-memory regressions on this
    next to rss_peak_mb."""
    from petastorm_tpu import membudget
    governor = membudget.get_governor()
    if not governor.armed:
        return None
    stats = governor.stats()
    return {'budget_bytes': stats['budget_bytes'],
            'budget_source': stats['budget_source'],
            'state': stats['state'],
            'peak_state': stats['peak_state'],
            'peak_frac': stats['peak_frac'],
            'accounted_bytes': stats['accounted_bytes'],
            'degrade_actions': stats['degrade_actions'],
            'breaches': stats['breaches']}


def _cache_tier_sweep(url, workers, batch, tiers):
    """Warm-epoch img/s + RSS per cache tier (ISSUE 5): the number that
    justifies the NVMe chunk-store tier is its warm rate staying near the
    RAM tier's while RSS stays flat (views over shared page cache, not
    per-process copies). ``null`` re-decodes every epoch (the cold floor),
    ``memory`` is the RAM ceiling, ``chunk-store`` is mmap-served NVMe.
    Fixed knobs (autotune off) so the tiers differ by exactly one thing."""
    measure = int(os.environ.get('BENCH_PIPELINE_TIER_BATCHES', '16'))
    warm = _IMAGENET_ROWS // batch + 2
    out = {}
    # A fleet-wide PETASTORM_TPU_CHUNK_STORE would silently arm the 'null'
    # tier with a warm persistent store, corrupting the cold-floor row —
    # the sweep builds its own store explicitly, so mask the env.
    from petastorm_tpu import chunk_store as chunk_store_mod
    saved_env = os.environ.pop(chunk_store_mod.ENV_VAR, None)
    try:
        _run_cache_tier_sweep(url, workers, batch, tiers, warm, measure, out)
    finally:
        if saved_env is not None:
            os.environ[chunk_store_mod.ENV_VAR] = saved_env
    return out


def _run_cache_tier_sweep(url, workers, batch, tiers, warm, measure, out):
    import shutil
    import tempfile as tempfile_mod

    import jax

    from petastorm_tpu import make_tensor_reader
    from petastorm_tpu.jax_loader import JaxLoader

    for tier in [t.strip() for t in tiers if t.strip()]:
        store_dir = None
        kwargs = {'cache_type': tier}
        if tier == 'chunk-store':
            store_dir = tempfile_mod.mkdtemp(prefix='pst-chunk-store-bench-')
            kwargs['cache_location'] = store_dir
        try:
            _measure_cache_tier(url, workers, batch, warm, measure,
                                kwargs, out, tier)
        except Exception as e:  # noqa: BLE001 - one bad tier (typo'd name)
            # must not discard the whole child's already-measured results
            out[tier] = {'error': '{}: {}'.format(type(e).__name__, e)}
        finally:
            if store_dir:
                shutil.rmtree(store_dir, ignore_errors=True)
    return out


def _measure_cache_tier(url, workers, batch, warm, measure, kwargs, out, tier):
    import jax

    from petastorm_tpu import make_tensor_reader
    from petastorm_tpu.jax_loader import JaxLoader

    reader = make_tensor_reader(
        url, schema_fields=['image', 'label'],
        reader_pool_type='thread', workers_count=workers,
        num_epochs=None, shuffle_row_groups=True, seed=0, **kwargs)
    with reader:
        with JaxLoader(reader, batch, prefetch=2, autotune=False) as loader:
            it = iter(loader)
            for _ in range(warm):
                b = next(it)
            jax.block_until_ready(b.image)
            store = reader.chunk_store
            flush_timed_out = False
            if store is not None:
                # The warm window must measure mmap serves, not a
                # still-draining write-behind queue.
                flush_timed_out = not store.flush()
            t0 = time.perf_counter()
            for _ in range(measure):
                b = next(it)
            jax.block_until_ready(b.image)
            record = {
                'img_per_sec': round(
                    batch * measure / (time.perf_counter() - t0), 2),
                'rss_mb': _rss_mb(),
                'rss_peak_mb': _peak_rss_mb()}
            if store is not None:
                st = store.stats()
                record['chunk_store'] = {
                    k: st[k] for k in ('hits', 'misses', 'fills', 'writes',
                                       'corrupt_quarantined')}
                if flush_timed_out:
                    # The window above mixed mmap serves with still-
                    # draining write-behind IO: the number is suspect.
                    record['flush_timed_out'] = True
    out[tier] = record


def _decode_path_sweep(url):
    """Cold-path img/s per decode path (ISSUE 13): ``scalar`` (one native
    call per image — the pre-batched behavior), ``batched`` (one native
    call per (row-group, field), fanned across the decode-thread budget),
    and ``chunk-store-warm`` (pre-transcoded via ``tools.transcode`` — no
    JPEG ever touched). Decode-bound protocol: ONE pool worker and a cold
    cache, so the scalar row is a single decode thread and the batched
    row is that worker spending the whole thread budget — the per-worker
    speedup 2605.08731's single-thread analysis says is recoverable. The
    ``ratio_batched_vs_scalar`` >= ``gate_min_ratio`` (1.5x) acceptance
    gate rides the stage profile."""
    import shutil
    import tempfile as tempfile_mod

    from petastorm_tpu import make_tensor_reader
    from petastorm_tpu.codecs import DECODE_PATH_ENV
    from petastorm_tpu.tools.transcode import transcode_dataset

    workers = int(os.environ.get('BENCH_PIPELINE_DECODE_WORKERS', '1'))
    out = {'workers': workers}

    def _measure(**reader_kwargs):
        reader = make_tensor_reader(
            url, schema_fields=['image', 'label'],
            reader_pool_type='thread', workers_count=workers,
            num_epochs=1, shuffle_row_groups=False, autotune=False,
            **reader_kwargs)
        with reader:
            t0 = time.perf_counter()
            images = sum(len(chunk.image) for chunk in reader)
            elapsed = time.perf_counter() - t0
            timings = dict(reader.stage_timings)
        return {'img_per_sec': round(images / elapsed, 2),
                'images': images,
                'wall_s': round(elapsed, 4),
                'read_s': round(timings.get('read_s', 0.0), 4),
                'decode_s': round(timings.get('decode_s', 0.0), 4)}

    saved = os.environ.get(DECODE_PATH_ENV)
    store_dir = tempfile_mod.mkdtemp(prefix='pst-chunk-store-decode-sweep-')
    try:
        os.environ[DECODE_PATH_ENV] = 'scalar'
        out['scalar'] = _measure(cache_type='null')
        os.environ[DECODE_PATH_ENV] = 'batched'
        out['batched'] = _measure(cache_type='null')
        transcode_dataset(url, store_dir, schema_fields=['image', 'label'],
                          workers_count=max(2, workers))
        out['chunk-store-warm'] = _measure(cache_type='chunk-store',
                                           cache_location=store_dir)
    except Exception as e:  # noqa: BLE001 - a failed sweep row must not
        # discard the child's already-measured results
        out['error'] = '{}: {}'.format(type(e).__name__, e)
    finally:
        if saved is None:
            os.environ.pop(DECODE_PATH_ENV, None)
        else:
            os.environ[DECODE_PATH_ENV] = saved
        shutil.rmtree(store_dir, ignore_errors=True)
    scalar_rate = (out.get('scalar') or {}).get('img_per_sec')
    batched_rate = (out.get('batched') or {}).get('img_per_sec')
    if scalar_rate and batched_rate:
        out['ratio_batched_vs_scalar'] = round(batched_rate / scalar_rate, 4)
        out['gate_min_ratio'] = 1.5
        out['gate_passed'] = out['ratio_batched_vs_scalar'] >= 1.5
    return out


def _per_device_stream_probe(url, workers, batch):
    """Streamed per-device dispatch window for the pipeline stage profile
    (ISSUE 17 satellite): a short mesh-sharded run with the inline tier
    disabled (``device_stream_min_bytes=0`` routes every field through the
    dispatch streams as batched wave items), so ``h2d_overlap_frac`` here
    is the stager OverlapMeter's host/H2D co-activity on the STREAMED
    path — the quantity the one-shot ``_measure_h2d`` probe structurally
    reports as 0.0."""
    import jax
    from petastorm_tpu import make_tensor_reader
    from petastorm_tpu.jax_loader import JaxLoader
    from petastorm_tpu.parallel import make_mesh

    measure = int(os.environ.get('BENCH_PIPELINE_STREAM_BATCHES', '16'))
    devices = jax.devices()
    n_dev = max(d for d in range(1, len(devices) + 1) if batch % d == 0)
    mesh = make_mesh({'data': n_dev}, devices=devices[:n_dev])
    reader = make_tensor_reader(
        url, schema_fields=['image', 'label'], reader_pool_type='thread',
        workers_count=workers, num_epochs=None, shuffle_row_groups=True,
        seed=0, cache_type='memory')
    with reader:
        with JaxLoader(reader, batch, mesh=mesh, autotune=False,
                       device_stream_min_bytes=0) as loader:
            it = iter(loader)
            for _ in range(4):
                b = next(it)
            jax.block_until_ready(b.image)
            loader.reset_stats()
            t0 = time.perf_counter()
            for _ in range(measure):
                b = next(it)
            jax.block_until_ready(b.image)
            elapsed = time.perf_counter() - t0
            stats = loader.stats
    put_s = stats.get('device_put_s') or {}
    put_bytes = stats.get('device_put_bytes') or {}
    return {
        'n_devices': stats.get('n_devices'),
        'img_per_sec': round(batch * measure / elapsed, 2),
        'h2d_overlap_frac': stats.get('h2d_overlap_frac'),
        'shards_put': stats.get('shards_put'),
        'device_stream_min_bytes': 0,
        'per_device_h2d_GBps': {
            dev: (round(put_bytes.get(dev, 0) / s / 1e9, 3) if s else None)
            for dev, s in put_s.items()},
        'arena_pinned': stats.get('arena_pinned'),
        'measure_batches': measure,
    }


def _child_pipeline(url, workers, cache_tiers=None):
    """Loader-only pipeline capacity (VERDICT r4 #2): the same tensor reader +
    JaxLoader path as the imagenet child but with NO train step — measures how
    many img/s the input pipeline can produce when nothing consumes compute.
    This is the number that answers "can the pipeline feed N img/s/chip";
    the train-loop stall fraction only bounds it against one model's step
    time. Mirrors the reference's reader-only throughput quantity
    (``petastorm/benchmark/throughput.py:94-110``). Host-side work dominates,
    so the number is meaningful even when jax runs on CPU.

    Load-controlled protocol: the child records loadavg around the
    measurement and reports the MEDIAN of N >= 3 repetition windows plus
    their spread — a shared host's throughput swings with load, and a
    single draw made cross-round host-capacity diffs noise."""
    import jax

    from petastorm_tpu import make_tensor_reader
    from petastorm_tpu.jax_loader import JaxLoader
    from petastorm_tpu.utils import enable_compile_cache

    enable_compile_cache()

    batch = int(os.environ.get('BENCH_PIPELINE_BATCH', '128'))
    warm_batches = max(1, int(os.environ.get(
        'BENCH_PIPELINE_WARMUP', str(_IMAGENET_ROWS // batch + 2))))
    measure_batches = int(os.environ.get('BENCH_PIPELINE_BATCHES', '32'))
    # prefetch > 0 engages the pipelined staging engine (recycled arenas +
    # assemble/dispatch overlap — the ISSUE 2 tentpole); 0 recovers the old
    # serial consumer-staging measurement for comparison.
    prefetch = int(os.environ.get('BENCH_PIPELINE_PREFETCH', '2'))
    # The autotuner (ISSUE 4) runs by default so the capacity number is the
    # self-configured one; BENCH_PIPELINE_AUTOTUNE=0 recovers fixed knobs,
    # and the *_ARENA_DEPTH/_INFLIGHT envs set deliberately bad starting
    # points for the convergence experiment.
    autotune_on = os.environ.get('BENCH_PIPELINE_AUTOTUNE', '1') == '1'
    arena_depth = os.environ.get('BENCH_PIPELINE_ARENA_DEPTH')
    inflight = int(os.environ.get('BENCH_PIPELINE_INFLIGHT', '2'))
    reps = max(1, int(os.environ.get('BENCH_PIPELINE_REPS', '3')))

    load_before = os.getloadavg()
    reader = make_tensor_reader(
        url, schema_fields=['image', 'label'],
        reader_pool_type='thread', workers_count=workers,
        num_epochs=None, shuffle_row_groups=True, seed=0,
        cache_type='memory')
    # Provenance ledger (ISSUE 7): armed with a throwaway dir so the
    # stage profile can report record counts + a replay self-check.
    from petastorm_tpu import lineage as lineage_mod
    ledger_dir = tempfile.mkdtemp(prefix=lineage_mod.TEMP_DIR_PREFIX)
    with reader:
        with JaxLoader(reader, batch, prefetch=prefetch,
                       inflight=inflight,
                       arena_depth=(int(arena_depth)
                                    if arena_depth else None),
                       autotune=autotune_on,
                       lineage=ledger_dir) as loader:
            it = iter(loader)
            # Warm through one epoch: decoded RAM cache fills, so the
            # steady-state number isolates pipeline mechanics from
            # first-epoch jpeg decode (reported separately below).
            t0 = time.perf_counter()
            for _ in range(warm_batches):
                b = next(it)
            jax.block_until_ready(b.image)
            cold_rate = batch * warm_batches / (time.perf_counter() - t0)
            t_read0 = dict(reader.stage_timings)
            # One stats window covering ALL reps: per-rep rates come
            # from per-rep wall clocks, while the stage profile stays
            # internally consistent (read/decode/cache deltas, loader
            # counters, and wall_s all span the same reps x batches).
            loader.reset_stats()
            rates = []
            wall_s = 0.0
            for _ in range(reps):
                start = time.perf_counter()
                for _ in range(measure_batches):
                    b = next(it)
                jax.block_until_ready(b.image)
                elapsed = time.perf_counter() - start
                wall_s += elapsed
                rates.append(batch * measure_batches / elapsed)
            stats = loader.stats
            t_read = stats.get('worker_stage_timings', {})
    # Deterministic-mode overhead (ISSUE 8): the same pipeline with
    # deterministic=True (Feistel epoch order + consumer-side
    # resequencer); the >= 0.7 acceptance gate reads the det/default
    # ratio. BENCH_PIPELINE_DETERMINISM=0 skips.
    det_rate = None
    if os.environ.get('BENCH_PIPELINE_DETERMINISM', '1') == '1':
        det_reader = make_tensor_reader(
            url, schema_fields=['image', 'label'],
            reader_pool_type='thread', workers_count=workers,
            num_epochs=None, shuffle_row_groups=True, seed=0,
            cache_type='memory', deterministic=True)
        with det_reader:
            with JaxLoader(det_reader, batch, prefetch=prefetch,
                           inflight=inflight) as det_loader:
                det_it = iter(det_loader)
                for _ in range(warm_batches):
                    b = next(det_it)
                jax.block_until_ready(b.image)
                start = time.perf_counter()
                for _ in range(measure_batches):
                    b = next(det_it)
                jax.block_until_ready(b.image)
                det_rate = batch * measure_batches / (time.perf_counter()
                                                      - start)
    load_after = os.getloadavg()
    ranked = sorted(rates)   # `rates` itself stays in measurement order:
                             # the reps list is the convergence trajectory
    middle = len(ranked) // 2
    median = (ranked[middle] if len(ranked) % 2
              else (ranked[middle - 1] + ranked[middle]) / 2)
    profile = {k: round(t_read.get(k, 0) - t_read0.get(k, 0), 4)
               for k in ('read_s', 'decode_s', 'cache_s')}
    profile['stage_dispatch_s'] = stats['stage_dispatch_s']
    profile['consumer_wait_s'] = stats['wait_s']
    profile['wall_s'] = round(wall_s, 4)
    profile.update(_staging_counters(stats))
    profile.update(_robustness_counters(stats))
    profile['rss_mb'] = _rss_mb()
    profile['rss_peak_mb'] = _peak_rss_mb()
    mem_rec = _mem_governor_summary()
    if mem_rec is not None:
        profile['mem'] = mem_rec
    profile['metrics'] = _metrics_snapshot()
    lineage_rec = _lineage_summary(loader, ledger_dir)
    if lineage_rec is not None:
        profile['lineage'] = lineage_rec
    if det_rate is not None:
        profile['determinism'] = {
            'img_per_sec': round(det_rate, 2),
            'default_img_per_sec': round(median, 2),
            'ratio_vs_default': round(det_rate / median, 4) if median else None}
    # Cache-tier sweep (ISSUE 5): --cache-tiers=null,memory,chunk-store on
    # the child command line, or BENCH_PIPELINE_CACHE_TIERS in the env.
    cache_tiers = cache_tiers or os.environ.get('BENCH_PIPELINE_CACHE_TIERS')
    if cache_tiers:
        profile['cache_tier_sweep'] = _cache_tier_sweep(
            url, workers, batch, cache_tiers.split(','))
    # Decode-path sweep (ISSUE 13): scalar vs batched vs chunk-store-warm
    # on the decode-bound (1-worker, cold-cache) config, with the 1.5x
    # batched-vs-scalar ratio gate. On by default so every BENCH round
    # records the decode block; BENCH_PIPELINE_DECODE_SWEEP=0 skips.
    if os.environ.get('BENCH_PIPELINE_DECODE_SWEEP', '1') == '1':
        profile['decode_path_sweep'] = _decode_path_sweep(url)
    # Streamed per-device dispatch (ISSUE 17): overlap + per-device h2d on
    # the batched-put stream tier. BENCH_PIPELINE_PER_DEVICE=0 skips.
    if os.environ.get('BENCH_PIPELINE_PER_DEVICE', '1') == '1':
        profile['per_device_stream'] = _per_device_stream_probe(
            url, workers, batch)
    out = {
        'pipeline_img_per_sec': round(median, 2),
        'pipeline_img_per_sec_reps': [round(r, 2) for r in rates],
        'pipeline_img_per_sec_spread': round(ranked[-1] - ranked[0], 2),
        'pipeline_cold_img_per_sec': round(cold_rate, 2),
        'pipeline_batch': batch,
        'pipeline_prefetch': prefetch,
        'pipeline_load': {'loadavg_before': list(load_before),
                          'loadavg_after': list(load_after),
                          'repetitions': reps},
        'pipeline_stage_profile': profile,
        'platform': jax.devices()[0].platform}
    autotune_rec = _autotune_summary(stats)
    if autotune_rec is not None:
        out['pipeline_autotune'] = autotune_rec
    print(json.dumps(out))


def _child_multichip(url, workers):
    """Per-device sharded dispatch on the forced 8-device CPU platform
    (ISSUE 14): the REAL multi-device path — per-device shard assembly,
    one overlapped ``device_put`` stream per device, global ``jax.Array``
    stitched with ``make_array_from_single_device_arrays`` — measured
    against (a) the one-shot ``make_array_from_process_local_data`` path
    on the SAME 8-device config (gate: >= 1.0x) and (b) the per-device
    path on ONE device (the scaling-efficiency ratio). Records
    ``n_devices`` and per-device ``h2d_GBps`` from the loader's
    per-stream put accounting. BENCH_SUMMARY keeps its single-chip
    basis; this child's numbers live under their own key."""
    # The whole point is n_devices > 1: force the virtual 8-device CPU
    # platform BEFORE any jax import initializes a backend.
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    xla_flags = os.environ.get('XLA_FLAGS', '')
    if 'xla_force_host_platform_device_count' not in xla_flags:
        os.environ['XLA_FLAGS'] = (
            xla_flags + ' --xla_force_host_platform_device_count=8').strip()
    import jax

    from petastorm_tpu import make_tensor_reader
    from petastorm_tpu.jax_loader import JaxLoader
    from petastorm_tpu.parallel import make_mesh
    from petastorm_tpu.utils import enable_compile_cache

    enable_compile_cache()
    batch = int(os.environ.get('BENCH_MULTICHIP_BATCH', '128'))
    warm_batches = max(1, int(os.environ.get(
        'BENCH_MULTICHIP_WARMUP', str(_IMAGENET_ROWS // batch + 2))))
    # Window sizing: at ~70k img/s a 48-batch window is ~90ms — short
    # windows (<20ms) made the interleaved ratio a scheduler-noise draw.
    measure_batches = int(os.environ.get('BENCH_MULTICHIP_BATCHES', '48'))
    reps = max(1, int(os.environ.get('BENCH_MULTICHIP_REPS', '5')))

    from statistics import median as _median

    def open_pipeline(n_devices, per_device):
        mesh = make_mesh({'data': n_devices},
                         devices=jax.devices()[:n_devices])
        reader = make_tensor_reader(
            url, schema_fields=['image', 'label'],
            reader_pool_type='thread', workers_count=workers,
            num_epochs=None, shuffle_row_groups=True, seed=0,
            cache_type='memory')
        loader = JaxLoader(reader, batch, mesh=mesh, autotune=False,
                           per_device_dispatch=per_device)
        it = iter(loader)
        for _ in range(warm_batches):
            b = next(it)
        jax.block_until_ready(b.image)
        loader.reset_stats()
        return reader, loader, it

    def window(it):
        t0 = time.perf_counter()
        for _ in range(measure_batches):
            b = next(it)
        jax.block_until_ready(b.image)
        return batch * measure_batches / (time.perf_counter() - t0)

    # The >= 1.0x gate compares the per-device path against the one-shot
    # path: ALTERNATE their measurement windows so shared-box load drift
    # (this host's throughput swings severalfold) hits both sides of the
    # ratio, not whichever config happened to run second.
    reader_pd, loader_pd, it_pd = open_pipeline(8, None)
    reader_os, loader_os, it_os = open_pipeline(8, False)
    rates_pd, rates_os = [], []
    try:
        for _ in range(reps):
            rates_pd.append(window(it_pd))
            rates_os.append(window(it_os))
        stats8 = loader_pd.stats
        stats_one_shot = loader_os.stats
    finally:
        # JaxLoader.stop() stops and joins its reader too.
        loader_pd.stop()
        loader_os.stop()
    rate8, rate_one_shot = _median(rates_pd), _median(rates_os)

    _reader_1, loader_1, it_1 = open_pipeline(1, None)
    try:
        rate1 = _median([window(it_1) for _ in range(reps)])
    finally:
        loader_1.stop()

    # Per-device h2d bandwidth: each stream's cumulative put bytes over
    # its cumulative put seconds (issue-side; the CPU "h2d" is a memcpy,
    # on a real pod host this is the PCIe rate per chip).
    put_s = stats8.get('device_put_s') or {}
    put_bytes = stats8.get('device_put_bytes') or {}
    h2d = {dev: (round(put_bytes.get(dev, 0) / seconds / 1e9, 3)
                 if seconds else None)
           for dev, seconds in put_s.items()}
    # The gate certifies the per-device path CARRIED the dispatch, not
    # just that a loader labeled 8 devices matched one-shot throughput:
    # every measured batch must have put at least one planned field's 8
    # shards (a silent full fallback to one-shot would report ~1.0x and
    # pass otherwise).
    engaged = (stats8.get('shards_put') or 0) >= measure_batches * reps * 8
    profile = {
        'n_devices': stats8.get('n_devices'),
        'per_device_engaged': engaged,
        'img_per_sec': round(rate8, 2),
        'one_shot_img_per_sec': round(rate_one_shot, 2),
        'ratio_per_device_vs_one_shot': (round(rate8 / rate_one_shot, 4)
                                         if rate_one_shot else None),
        'gate_min_ratio': 1.0,
        'gate_passed': (engaged and bool(rate_one_shot)
                        and rate8 >= rate_one_shot),
        'img_per_sec_1dev': round(rate1, 2),
        'scaling_ratio_8dev_vs_1dev': (round(rate8 / rate1, 4)
                                       if rate1 else None),
        'per_device_h2d_GBps': h2d,
        # The measured host-memcpy ceiling is the bandwidth any
        # memcpy-based put cannot beat — per-device h2d_GBps against it
        # makes the dispatch gap a number, not a vibe (on a real pod the
        # comparison is per-chip PCIe vs host DRAM).
        'host_memcpy_ceiling_GBps': _memcpy_ceiling(),
        'h2d_overlap_frac': stats8.get('h2d_overlap_frac'),
        'shards_put': stats8.get('shards_put'),
        'shards_donated': stats8.get('shards_donated'),
        'device_inflight': stats8.get('device_inflight'),
        'device_ready_wait_s': stats8.get('device_ready_wait_s'),
        'stage_dispatch_s': stats8.get('stage_dispatch_s'),
        'one_shot_stage_dispatch_s': stats_one_shot.get('stage_dispatch_s'),
        'batch': batch,
        'measure_batches': measure_batches,
        'repetitions': reps,
    }
    print(json.dumps({'multichip_stage_profile': profile,
                      'platform': jax.devices()[0].platform}))


def _ensure_lookup_dataset():
    """Imagenet-shaped rows with a UNIQUE integer key ('idx') plus the
    row-level index over it — the point-read workload of the online
    lookup tier (ISSUE 15). Separate from the imagenet bench store: that
    one has no unique key field, and an index build would mutate its
    _common_metadata under the other children."""
    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.etl.rowgroup_indexers import SingleFieldRowIndexer
    from petastorm_tpu.etl.rowgroup_indexing import build_rowgroup_index
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.unischema import Unischema, UnischemaField

    lookup_dir = '/tmp/petastorm_tpu_bench_lookup_r{}'.format(_LOOKUP_ROWS)
    url = 'file://' + lookup_dir
    if os.path.exists(os.path.join(lookup_dir, '_common_metadata')):
        # Readiness must cover the INDEX too: a run killed between
        # write_dataset and build_rowgroup_index leaves the metadata file
        # without the row-level index, which would wedge every later
        # bench run on 'has no row-group index'. The dataset files are
        # fine in that case — just (re)build the index.
        try:
            from petastorm_tpu.etl.rowgroup_indexing import \
                get_row_group_indexes
            if 'idx_row_ix' in get_row_group_indexes(url):
                return url
        except Exception:  # noqa: BLE001 - absent/partial index: rebuild
            pass
        build_rowgroup_index(url,
                             [SingleFieldRowIndexer('idx_row_ix', 'idx')])
        return url
    schema = Unischema('LookupBenchSchema', [
        UnischemaField('idx', np.int64, (), ScalarCodec(np.int64), False),
        UnischemaField('image', np.uint8, (_IMAGE_SIZE, _IMAGE_SIZE, 3),
                       CompressedImageCodec('jpeg', 90), False),
        UnischemaField('label', np.int64, (), ScalarCodec(np.int64), False),
    ])
    rng = np.random.default_rng(13)

    def rows():
        for i in range(_LOOKUP_ROWS):
            yield {'idx': i,
                   'image': _synthetic_image(rng, _IMAGE_SIZE),
                   'label': int(rng.integers(0, 1000))}

    write_dataset(url, schema, rows(),
                  rows_per_row_group=_LOOKUP_ROWS_PER_GROUP)
    build_rowgroup_index(url, [SingleFieldRowIndexer('idx_row_ix', 'idx')])
    return url


def _percentile_ms(samples, frac):
    """Nearest-rank percentile of a latency sample list, in ms."""
    ranked = sorted(samples)
    rank = max(0, min(len(ranked) - 1, int(round(frac * len(ranked))) - 1))
    return round(ranked[rank] * 1000.0, 3)


def _bench_lookup_fleet(url):
    """Fleet SLO leg of the lookup child (ISSUE 16): a 2-partition x
    2-replica fleet over loopback, reads storming while one member
    DRAINS mid-run (live reassignment: version bump, map push, client
    convergence). The gate is the robustness claim itself — warm p99
    stays under 10ms THROUGH the drain, with zero failed and zero
    truncated lookups. The joiner warm-fills its chunk store from the
    donor over the ``chunk`` verb, so both replicas serve store-warm
    from the first read."""
    from petastorm_tpu.serving import LookupClient, LookupEngine, LookupServer

    reads = int(os.environ.get('BENCH_LOOKUP_FLEET_READS', '300'))
    rng = np.random.default_rng(1)
    dirs = [tempfile.mkdtemp(prefix='pst-chunk-store-') for _ in range(2)]
    engines, servers = [], []
    try:
        engines = [LookupEngine(url, index_name='idx_row_ix', cache=d,
                                block_cache_entries=1) for d in dirs]
        # Warm the donor's store once (cold latency is the single-server
        # leg's business); packed_chunk fetches through the tier ladder.
        for piece in range(engines[0].piece_count):
            engines[0].packed_chunk(piece)
        assert engines[0].flush(60.0), 'donor store spill did not drain'
        servers = [LookupServer(eng, 'tcp://127.0.0.1:*', lease_s=1.0,
                                server_name=name).start()
                   for eng, name in zip(engines, ('bench-a', 'bench-b'))]
        servers[0].init_fleet(n_partitions=2, replication=2)
        join = servers[1].join_fleet(servers[0].rpc_endpoint, warm=True)
        lat = []
        failed = truncated = 0
        drain_at = reads // 2
        version_after_drain = None
        with LookupClient([s.rpc_endpoint for s in servers],
                          control_endpoints=[s.control_endpoint
                                             for s in servers],
                          timeout_ms=30000, hedge_after_ms=50) as client:
            client.refresh_partition_map()
            # Untimed warmup: touch every piece on EVERY replica (the
            # first read of a warm-filled chunk on a server pays its
            # mmap open — a one-time cost, not the warm path the gate
            # claims; without this the post-drain failover would hit
            # cold maps too).
            for server in servers:
                for key in range(0, _LOOKUP_ROWS, _LOOKUP_ROWS_PER_GROUP):
                    client._request_one(server.rpc_endpoint,
                                        {'cmd': 'lookup', 'keys': [key],
                                         'consumer': client._consumer_id},
                                        30000)
            for i in range(reads):
                if i == drain_at:
                    servers[0].drain()
                    version_after_drain = \
                        servers[1].partition_map.version
                key = int(rng.integers(0, _LOOKUP_ROWS))
                t0 = time.perf_counter()
                try:
                    rows = client.lookup([key])[0]
                except Exception:  # noqa: BLE001 - counted, gate fails
                    failed += 1
                    continue
                lat.append(time.perf_counter() - t0)
                if not rows or int(rows[0]['idx']) != key:
                    truncated += 1
            scatter = client.scatter_stats()
            # A short storm can finish inside one heartbeat interval —
            # converge explicitly so the profile proves the client SEES
            # the reassigned map, not just that it survived the drain.
            client.refresh_partition_map()
            client_version = (client.partition_map.version
                              if client.partition_map else None)
        p99 = _percentile_ms(lat, 0.99) if lat else None
        return {
            'n_partitions': 2,
            'replication': 2,
            'reads': reads,
            'drained_member_at_read': drain_at,
            'warm_p50_ms': _percentile_ms(lat, 0.50) if lat else None,
            'warm_p99_ms': p99,
            'failed_lookups': failed,
            'truncated_lookups': truncated,
            'warm_join': {k: join[k] for k in
                          ('warmed_chunks', 'warm_skipped',
                           'warm_failed')},
            'map_version_after_join': 2,
            'map_version_after_drain': version_after_drain,
            'client_map_version': client_version,
            'scatter': scatter,
            'p99_gate_ms': 10.0,
            'p99_gate_passed': (p99 is not None and p99 < 10.0
                                and failed == 0 and truncated == 0),
        }
    finally:
        for server in servers:
            server.stop()
        for eng in engines:
            eng.close()
        import shutil
        for d in dirs:
            shutil.rmtree(d, ignore_errors=True)


def _child_lookup():
    """Online lookup tier point-read SLO (ISSUE 15): warm/cold p50/p99 +
    cache hit rate through the FULL rpc path (LookupServer + LookupClient
    over tcp loopback) against the row-level index and a chunk-store hot
    tier. Load-controlled like the pipeline child: records loadavg and
    reports the MEDIAN of N >= 3 repetition windows — the p99 gate
    (< 10ms warm) is a latency claim on a shared VM, so a single draw
    would gate on scheduler noise.

    Warm reads are kept HONEST chunk-store hits: the engine's in-memory
    block LRU is pinned to one entry while keys randomize across every
    row-group, so ~(G-1)/G of warm reads pay the mmap + row-memcpy path
    the tier is named for (the hit-rate and tier counts in the profile
    prove it)."""
    from petastorm_tpu.serving import LookupClient, LookupEngine, LookupServer

    url = _ensure_lookup_dataset()
    reads = int(os.environ.get('BENCH_LOOKUP_READS', '200'))
    reps = max(1, int(os.environ.get('BENCH_LOOKUP_REPS', '3')))
    rng = np.random.default_rng(0)

    store_dir = tempfile.mkdtemp(prefix='pst-chunk-store-')
    try:
        load_before = os.getloadavg()
        engine = LookupEngine(url, index_name='idx_row_ix',
                              cache=store_dir, block_cache_entries=1)
        with engine:
            with LookupServer(engine,
                              'tcp://127.0.0.1:*').start() as server:
                with LookupClient([server.rpc_endpoint],
                                  timeout_ms=30000) as client:
                    # COLD: first touch of every row-group is a full
                    # read + jpeg-decode of the group (the miss path).
                    cold_keys = list(range(0, _LOOKUP_ROWS,
                                           _LOOKUP_ROWS_PER_GROUP))
                    cold = []
                    for key in cold_keys:
                        t0 = time.perf_counter()
                        assert client.lookup([int(key)])[0]
                        cold.append(time.perf_counter() - t0)
                    # Every block is now decoded; let the write-behind
                    # writer publish them so warm reads hit the store.
                    assert engine.flush(60.0), \
                        'chunk store spill did not drain'
                    warm_rates = []
                    warm_p50s, warm_p99s = [], []
                    for _ in range(reps):
                        keys = rng.integers(0, _LOOKUP_ROWS, reads)
                        warm = []
                        for key in keys:
                            t0 = time.perf_counter()
                            rows = client.lookup([int(key)])[0]
                            warm.append(time.perf_counter() - t0)
                            assert rows and int(rows[0]['idx']) == int(key)
                        warm_p50s.append(_percentile_ms(warm, 0.50))
                        warm_p99s.append(_percentile_ms(warm, 0.99))
                        warm_rates.append(reads / sum(warm))
                    tiers = engine.stats()['tiers']
                    store_stats = engine.stats().get('store') or {}
                    served = server.requests_served
        # Fleet SLO leg (ISSUE 16): the drain-through p99 is a latency
        # gate like the warm one above.
        fleet = _bench_lookup_fleet(url)
        load_after = os.getloadavg()
    finally:
        import shutil
        shutil.rmtree(store_dir, ignore_errors=True)
    total = sum(tiers.values()) or 1
    hot = sum(n for tier, n in tiers.items() if tier != 'decode')
    warm_p50 = statistics.median(warm_p50s)
    warm_p99 = statistics.median(warm_p99s)
    profile = {
        'warm_p50_ms': warm_p50,
        'warm_p99_ms': warm_p99,
        'warm_p99_ms_reps': warm_p99s,
        'warm_reads_per_sec': round(statistics.median(warm_rates), 1),
        'cold_p50_ms': _percentile_ms(cold, 0.50),
        'cold_p99_ms': _percentile_ms(cold, 0.99),
        'cold_reads': len(cold),
        'hit_rate': round(hot / total, 4),
        'tiers': tiers,
        'store': {k: store_stats.get(k) for k in
                  ('hits', 'misses', 'writes', 'bytes_mapped')},
        'requests_served': served,
        'reads_per_rep': reads,
        'repetitions': reps,
        'p99_gate_ms': 10.0,
        'p99_gate_passed': warm_p99 < 10.0,
        'fleet': fleet,
        'load': {'loadavg_before': list(load_before),
                 'loadavg_after': list(load_after)},
        'metrics': _metrics_snapshot(),
    }
    print(json.dumps({'lookup_stage_profile': profile, 'platform': 'cpu'}))


def _fleet_wire_server_proc(tier, chunk_rows, row_width, n_chunks,
                            out_q, stop_evt):
    """Server half of the ``fleet_wire`` bench child, in its OWN process.
    An in-process server would share the consumer's GIL and serialize
    the two ends' Python work — measured ~7x under the two-process rate
    and FLAT across tiers (the contention paces it, not the wire), which
    is also just not the deployment shape the tiers exist for. Puts the
    data endpoint on ``out_q`` at start and, once drained, this process's
    metrics snapshot (the server-side pst_wire_* counters live here).

    The serve loop is held (``_pause``) until the consumer's attach rpc
    is admitted: chunks encoded before the wire grant lands ride the
    empty-fleet tier (pickle), and with MB-scale chunks the attach
    window covers a large slice of the epoch — the pass would measure a
    pickle/shm blend instead of the granted tier. Real trainings attach
    every consumer before the epoch starts, so the gate matches the
    deployment shape."""
    import collections

    # Ring sized so capacity never forces mid-pass tier fallbacks: the
    # consumer prefetches up to ~16 chunks (HWM counts frames) and acks
    # trail by the flush cadence, so ~48 chunks of headroom keeps the
    # pass tier-pure without hiding ack flow entirely.
    ring_mb = max(64, (chunk_rows * row_width * 4 * 48) >> 20)
    os.environ.setdefault('PETASTORM_TPU_WIRE_SEGMENT_MB', str(ring_mb))

    from petastorm_tpu import data_service as ds

    class _StreamReader(object):
        """Minimal batched-reader surface (batched_output, namedtuple
        iteration, stop/join, diagnostics) serving synthetic columns —
        isolates the wire from parquet decode."""

        batched_output = True
        ngram = None

        def __iter__(self):
            nt = collections.namedtuple('WireChunk', ['vec', 'sid'])
            rng = np.random.default_rng(7)
            vec = rng.random((chunk_rows, row_width)).astype(np.float32)
            for i in range(n_chunks):
                yield nt(vec=vec,
                         sid=np.arange(i * chunk_rows, (i + 1) * chunk_rows,
                                       dtype=np.int64))

        def stop(self):
            pass

        def join(self):
            pass

        @property
        def diagnostics(self):
            return {}

    server = ds.DataServer(_StreamReader(), bind='tcp://127.0.0.1:*',
                           sndhwm=32, wire=tier)
    server._pause.set()     # hold the serve loop for the attach (above)
    server.start()
    out_q.put(server.data_endpoint)
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        with server._admission_lock:
            if server._admission.count_locked() >= 1:
                break
        time.sleep(0.005)
    server._pause.clear()
    stop_evt.wait(300)
    out_q.put(_metrics_snapshot())
    server.stop()


def _child_fleet_wire():
    """Negotiated data-plane wire throughput (ISSUE 20): the SAME synthetic
    chunk stream drained through the full service path (DataServer in its
    own process → RemoteReader over tcp loopback) once per transport tier
    — pickle, arrow-ipc, shm — each forced via the server's ``wire=`` cap
    so the negotiation can't upgrade a pass behind the bench's back.
    Records chunks/s and effective payload GB/s per tier plus the
    server's pst_wire_* counters, which prove the tier mix (a pass
    polluted by ring-full arrow fallbacks would show it) and the
    serialize cost (shm descriptors must be ~free). Gate: shm >= 2x
    pickle chunks/s — the tier's whole reason to exist is skipping the
    serialize + TCP double copy.

    The drain loop flushes wire acks inline every few chunks: the client
    control loop only flushes on its 0.25s tick, and a 64MB ring outruns
    that at bench rates — without prompt acks the shm pass would quietly
    degrade into an arrow benchmark. Rates are first-chunk -> last-chunk
    (end-of-stream bookkeeping excluded) and the MEDIAN of N >= 3
    repetitions: the 2x gate is a throughput claim on a shared VM, so a
    single draw would gate on scheduler noise (same discipline as the
    lookup child's p99 gate)."""
    import gc
    import multiprocessing

    from petastorm_tpu import data_service as ds
    from petastorm_tpu.fleet import wire as fleet_wire

    chunk_rows = int(os.environ.get('BENCH_WIRE_ROWS', '4096'))
    row_width = 1024            # float32 -> 4KB/row -> 16MB vec per chunk;
    # MB-scale chunks make the tiers' cost structures visible: pickle is
    # pinned at the TCP-loopback copy ceiling while shm pays only DRAM
    # passes, so the gap IS the tier — tiny chunks measure the shared
    # ~1ms/chunk pipeline overhead instead and every tier converges.
    n_chunks = int(os.environ.get('BENCH_WIRE_CHUNKS', '48'))
    reps = max(1, int(os.environ.get('BENCH_WIRE_REPS', '3')))
    chunk_bytes = chunk_rows * row_width * 4 + chunk_rows * 8
    mp = multiprocessing.get_context('spawn')

    def _run_tier(tier):
        out_q = mp.Queue()
        stop_evt = mp.Event()
        proc = mp.Process(target=_fleet_wire_server_proc,
                          args=(tier, chunk_rows, row_width, n_chunks,
                                out_q, stop_evt))
        proc.start()
        try:
            endpoint = out_q.get(timeout=120)
            reader = ds.RemoteReader(endpoint, rcvhwm=32)
            got = 0
            t0 = t_last = time.perf_counter()
            try:
                for chunk in reader:
                    assert chunk.vec.dtype == np.float32
                    assert chunk.vec.shape == (chunk_rows, row_width)
                    got += 1
                    t_last = time.perf_counter()
                    if got == 1:
                        t0 = t_last     # clock starts at the first chunk
                    del chunk   # release the shm region (refcount-exact)
                    if got % 4 == 0:
                        reader._flush_wire_acks()
                grant = next(iter(reader.fleet_metrics()['wire'].values()))
            finally:
                gc.collect()
                reader._flush_wire_acks()
                reader.stop()
                reader.join()
            stop_evt.set()
            server_metrics = out_q.get(timeout=60)
        finally:
            stop_evt.set()
            proc.join(30)
            if proc.is_alive():
                proc.terminate()
        assert got == n_chunks, (tier, got)
        # Rate over the (n-1) inter-chunk intervals: the first chunk
        # carries attach/negotiate latency and the end-of-stream END
        # handshake follows the last — neither is wire throughput.
        elapsed = max(t_last - t0, 1e-9)
        by_transport = {
            s['labels'].get('transport'): int(s['value'])
            for s in (server_metrics.get('pst_wire_bytes_total') or {}
                      ).get('samples', [])}
        ser = {'sum': 0.0, 'count': 0}
        for s in (server_metrics.get('pst_wire_serialize_seconds') or {}
                  ).get('samples', []):
            ser['sum'] += s.get('sum', 0.0)
            ser['count'] += s.get('count', 0)
        return {
            'granted': grant,
            'chunks': got,
            'chunks_per_sec': round((got - 1) / elapsed, 1),
            'payload_gb_per_sec': round(
                (got - 1) * chunk_bytes / elapsed / 1e9, 3),
            'wire_bytes_by_transport': by_transport,
            'serialize_ms_per_chunk': round(
                ser['sum'] / ser['count'] * 1e3, 4) if ser['count'] else None,
        }

    def _median_tier(tier):
        runs = [_run_tier(tier) for _ in range(reps)]
        runs.sort(key=lambda r: r['chunks_per_sec'])
        best = runs[len(runs) // 2]
        best['chunks_per_sec_reps'] = [r['chunks_per_sec'] for r in runs]
        return best

    load_before = os.getloadavg()
    tiers = {tier: _median_tier(tier) for tier in
             (fleet_wire.TRANSPORT_PICKLE, fleet_wire.TRANSPORT_ARROW,
              fleet_wire.TRANSPORT_SHM)}
    load_after = os.getloadavg()
    from petastorm_tpu.native import shm_ring
    leaked = shm_ring.list_segments(fleet_wire.SEGMENT_PREFIX)
    shm_rate = tiers[fleet_wire.TRANSPORT_SHM]['chunks_per_sec']
    pickle_rate = tiers[fleet_wire.TRANSPORT_PICKLE]['chunks_per_sec']
    profile = {
        'chunk_bytes': chunk_bytes,
        'chunks_per_epoch': n_chunks,
        'repetitions': reps,
        'tiers': tiers,
        'shm_over_pickle': round(shm_rate / pickle_rate, 2)
        if pickle_rate else None,
        'gate_min_ratio': 2.0,
        'gate_passed': shm_rate >= 2.0 * pickle_rate,
        'leaked_segments': leaked,
        'load': {'loadavg_before': list(load_before),
                 'loadavg_after': list(load_after)},
        'metrics': _metrics_snapshot(),
    }
    print(json.dumps({'fleet_wire_stage_profile': profile,
                      'platform': 'cpu'}))


def _child_flashattn():
    """Pallas flash attention on the real chip: correctness vs the dense XLA
    reference (fwd + input grads) and fwd+bwd step timings at long sequence
    lengths, bf16, causal. Inputs are generated ON DEVICE (no h2d beyond
    scalars) and every timing ends in ``block_until_ready``. An explicit
    CPU run checks the same kernels in the Pallas interpreter and says so
    (``interpret: true``); it takes no timings."""
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.models.attention import dense_attention
    from petastorm_tpu.ops.flash_attention import flash_attention
    from petastorm_tpu.utils import enable_compile_cache

    enable_compile_cache()
    platform = jax.devices()[0].platform
    interpret = platform != 'tpu'
    fence = jax.block_until_ready

    out = {'platform': platform, 'interpret': interpret}
    # Correctness at a size small enough for the dense [T,T] reference.
    B, T, H, D = 2, 512, 4, 64
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, T, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, T, H, D), jnp.float32)
    v = jax.random.normal(kv, (B, T, H, D), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=interpret) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    o_f = flash_attention(q, k, v, causal=True, interpret=interpret)
    o_d = dense_attention(q, k, v, causal=True)
    out['fwd_max_rel_err'] = round(
        float(jnp.max(jnp.abs(o_f - o_d)) / jnp.max(jnp.abs(o_d))), 6)
    g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    out['grad_max_rel_err'] = round(max(
        float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        for a, b in zip(g_f, g_d)), 6)

    # Timing sweep, bf16 causal fwd+bwd (the training shape). FLOPs for
    # causal attention: ~2 * 4*B*T^2/2*H*D fwd, x2.5 with bwd. TPU only:
    # a time from the interpreter is not a kernel time.
    timings = {}
    if interpret:
        out['flash_train_step'] = 'not measured: interpreter run'
        print(json.dumps(out))
        return
    for T in (int(s) for s in os.environ.get(
            'BENCH_FLASH_SEQ', '2048,8192,16384').split(',')):
        # Two shapes per length: B=1 (the r4 shape, kept for cross-round
        # comparability — fixed dispatch overhead weighs heavily on it) and
        # B=4 (a per-chip training microbatch; amortizes dispatch and fills
        # the grid's parallel axes — the capability number).
        for B, tag in ((1, 'T{}'), (4, 'T{}_b4')):
            kq, kk, kv = jax.random.split(jax.random.PRNGKey(T), 3)
            shape = (B, T, 8, 128)
            qb = jax.random.normal(kq, shape, jnp.bfloat16)
            kb = jax.random.normal(kk, shape, jnp.bfloat16)
            vb = jax.random.normal(kv, shape, jnp.bfloat16)
            step = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))
            fence(step(qb, kb, vb)[0])   # compile + land
            # B=1 keeps the r4 methodology exactly (single 8-rep pass) so
            # the T{N} keys stay comparable across rounds; the new _b4
            # series takes best-of-2 16-rep passes (first pass can carry
            # scheduler stragglers).
            reps, passes = (8, 1) if B == 1 else (16, 2)
            dt = None
            for _ in range(passes):
                t0 = time.perf_counter()
                for _ in range(reps - 1):
                    g = step(qb, kb, vb)
                fence(step(qb, kb, vb)[0])
                cur = (time.perf_counter() - t0) / reps
                dt = cur if dt is None else min(dt, cur)
            flops = 2.5 * 4 * shape[0] * T * T * shape[2] * shape[3]  # causal halves, fwd+bwd ~2.5x
            timings[tag.format(T)] = {
                'fwd_bwd_ms': round(dt * 1e3, 2),
                'tflops_per_s': round(flops / dt / 2 / 1e12, 2)}
    out['flash_train_step'] = timings
    print(json.dumps(out))


def _memcpy_ceiling():
    """Measured sustained host-memcpy bandwidth in GB/s (the native
    probe in ``native/pinned.py``; ``None`` when the measurement failed)
    — the ceiling any memcpy-based h2d path is chasing."""
    try:
        from petastorm_tpu.native import pinned as pinned_mod
        gbps = pinned_mod.memcpy_ceiling_GBps()
        return round(gbps, 3) if gbps else None
    except Exception:  # noqa: BLE001 - a probe must never kill a bench
        return None


def _measure_h2d(jax, batch):
    """h2d probes: one-shot latency, sustained double-buffered bandwidth, the
    overlap fraction of transfers hidden under a jitted compute (VERDICT r2
    next-round #7), and the chunked-put rate (``stage_chunks`` staging).

    Every timing ends in ``block_until_ready``; ``chip_smoke.py`` checks on
    the chip that it waits for the transfer (a byte pulled back afterwards
    costs no more than from a resident array)."""
    import jax.numpy as jnp
    fence = jax.block_until_ready

    buf = np.ones((batch, _IMAGE_SIZE, _IMAGE_SIZE, 3), np.uint8)
    fence(jax.device_put(buf))  # warm the transfer path
    resident = jax.device_put(buf)
    fence(resident)
    t0 = time.perf_counter()
    fence(resident)
    fence_s = time.perf_counter() - t0   # round-trip floor, no fresh h2d
    t0 = time.perf_counter()
    fence(jax.device_put(buf))
    oneshot_gbps = buf.nbytes / max(1e-9, time.perf_counter() - t0 - fence_s) / 1e9

    # Sustained: keep 2 transfers in flight, 8 total (steady-state rate, not
    # first-transfer latency); fence each as it retires.
    bufs = [buf, buf + 1]
    n = 8
    t0 = time.perf_counter()
    inflight = []
    for i in range(n):
        inflight.append(jax.device_put(bufs[i % 2]))
        if len(inflight) > 2:
            fence(inflight.pop(0))
    for a in inflight:
        fence(a)
    sustained_gbps = buf.nbytes * n / (time.perf_counter() - t0) / 1e9

    # Chunked put (what JaxLoader(stage_chunks=k) does): split along the
    # batch dim, put the pieces, concatenate on device.
    cat = jax.jit(lambda *xs: jnp.concatenate(xs))
    k = 4
    parts = np.array_split(buf, k)
    fence(cat(*[jax.device_put(p) for p in parts]))  # warm concat
    t0 = time.perf_counter()
    fence(cat(*[jax.device_put(p) for p in parts]))
    chunked_gbps = buf.nbytes / max(1e-9, time.perf_counter() - t0 - fence_s) / 1e9

    # Overlap: does a transfer hide under compute? compare compute-only vs
    # compute+concurrent device_put wall time.
    x = jax.device_put(np.ones((2048, 2048), np.float32))
    matmul = jax.jit(lambda a: a @ a)
    mfence = jax.block_until_ready
    mfence(matmul(x))
    t0 = time.perf_counter()
    for _ in range(4):
        mfence(matmul(x))
    compute_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for i in range(4):
        y = matmul(x)
        h = jax.device_put(bufs[i % 2])
        mfence(y)
        fence(h)
    both_s = time.perf_counter() - t0
    xfer_s = buf.nbytes * 4 / (sustained_gbps * 1e9)
    added = max(0.0, both_s - compute_s)
    overlap_frac = max(0.0, min(1.0, 1.0 - added / xfer_s)) if xfer_s > 0 else 0.0
    return {'h2d_GBps': round(oneshot_gbps, 3),
            'h2d_sustained_GBps': round(sustained_gbps, 3),
            'h2d_chunked_GBps': round(chunked_gbps, 3),
            'h2d_fence_rtt_ms': round(fence_s * 1e3, 1),
            'host_memcpy_ceiling_GBps': _memcpy_ceiling(),
            'h2d_overlap_frac': round(overlap_frac, 3)}


# Peak dense bf16 FLOP/s of one chip, keyed by the exact ``device_kind`` jax
# reports. Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16 per
# chip); the key is what jax 0.9.0 / libtpu 0.0.34 print for that chip. A
# device that is not in the table is an error, never a guess.
_PEAK_BF16_FLOPS = {'TPU v5 lite': 197e12}


def _peak_bf16_flops(device):
    """Per-chip peak bf16 matmul FLOP/s of ``device``; raises for a
    ``device_kind`` the table does not hold."""
    try:
        return _PEAK_BF16_FLOPS[device.device_kind]
    except KeyError:
        raise ValueError(
            'no peak FLOP/s on record for device_kind {!r}; add it to '
            'bench._PEAK_BF16_FLOPS with its source'.format(
                device.device_kind)) from None


# Forward-pass FLOPs per 224x224x3 image (the standard published counts);
# train step ~= 3x forward (bwd is ~2x fwd for convnets).
# resnet: published counts. vit: analytic for this repo's ViT default
# (patch 16, d=384, 8 layers, mlp x4 — ViT-S-ish at 2/3 depth) on 224^2:
# per layer 2*(4*T*d^2 + 2*T^2*d + 8*T*d^2) with T=197, plus patchify
# (196*384*768 MACs) and the 1000-way head = ~6.2e9 fwd FLOPs.
_MODEL_FWD_FLOPS = {'resnet50': 4.09e9, 'resnet18': 1.82e9, 'vit': 6.2e9}

# The space_to_depth stem retires more stem MACs than the classic 7x7/2 it
# replaces (4x4 conv over the 2x2-packed 112x112x12 input: 4*4*12*64 =
# 12288 MACs per output pixel vs 7*7*3*64 = 9408), so the s2d variant's
# MFU must use its own FLOP basis or cross-stem comparisons are ~2% off.
# Published resnet counts assume conv7; add the delta.
_S2D_STEM_EXTRA_FLOPS = 2 * (12288 - 9408) * 112 * 112


def _model_fwd_flops(model_name, stem):
    """Analytic forward FLOPs for (model, stem), or None when unknown."""
    fwd = _MODEL_FWD_FLOPS.get(model_name)
    if fwd is not None and stem == 'space_to_depth':
        fwd += _S2D_STEM_EXTRA_FLOPS
    return fwd

# Training retires ~3x the forward FLOPs (fwd + bwd at 2x) — the standard
# analytic-MFU convention; an intentional lower bound (ignores batch norm
# and optimizer element-wise work).
_TRAIN_FLOP_MULT = 3


def _mfu(fwd_flops_per_img, img_per_sec_per_chip, peak_flops_per_chip,
         mult=_TRAIN_FLOP_MULT):
    """Model FLOPs utilization for one chip: analytic model FLOPs actually
    retired per second over the chip's peak. Single definition — the child
    record, the HBM-cached auxiliary metric, and the fold's back-fill for
    older records must always agree."""
    return round(mult * fwd_flops_per_img * img_per_sec_per_chip
                 / peak_flops_per_chip, 4)


def _child_imagenet(url, workers):
    """North star: jpeg Parquet -> decoded-columnar tensor reader (native C++
    batch decode into contiguous blocks, decoded-chunk RAM cache) ->
    JaxLoader block fast path -> jitted ResNet-50 train step; img/s/chip +
    input_stall_frac + per-stage profile."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from petastorm_tpu import make_tensor_reader
    from petastorm_tpu.jax_loader import JaxLoader
    from petastorm_tpu.models import resnet
    from petastorm_tpu.models.train import (create_train_state,
                                            make_scan_train_step,
                                            make_train_step)
    from petastorm_tpu.parallel import make_mesh
    from petastorm_tpu.utils import enable_compile_cache

    enable_compile_cache()

    # Env overrides exist so CI can smoke the full path on CPU with a tiny
    # model; the real bench uses the defaults.
    batch = int(os.environ.get('BENCH_IMAGENET_BATCH', '128'))
    # Steady-state measurement: warm through one full epoch so the decoded
    # RAM cache is populated and first-compile is done — the north star is
    # sustained training throughput, not cold-start (first epoch decode rate
    # is reported separately by the host-side stage profile).
    warmup_steps = int(os.environ.get(
        'BENCH_IMAGENET_WARMUP', str(_IMAGENET_ROWS // batch + 3)))
    measure_steps = int(os.environ.get('BENCH_IMAGENET_STEPS', '40'))
    from petastorm_tpu.models import vit
    model_cls = {'resnet50': resnet.ResNet50, 'resnet18': resnet.ResNet18,
                 'tiny': resnet.ResNetTiny,
                 'vit': vit.ViT}[os.environ.get('BENCH_IMAGENET_MODEL', 'resnet50')]
    n_devices = jax.device_count()
    platform = jax.devices()[0].platform

    h2d = _measure_h2d(jax, batch)

    # Multi-device hosts get a data-parallel mesh over every chip so the
    # per-chip division below is honest; batch scales to keep 128/chip.
    mesh = make_mesh({'data': n_devices}) if n_devices > 1 else None
    batch = batch * n_devices

    model_kwargs = {'num_classes': 1000}
    model_name = os.environ.get('BENCH_IMAGENET_MODEL', 'resnet50')
    if model_name != 'vit':
        # 'space_to_depth' rearranges 2x2 pixel blocks into channels before
        # an equivalent 4x4/1 stem conv — the MLPerf ResNet-on-TPU stem
        # (C=3 starves the MXU's 128-lane tiling in the classic 7x7/2).
        model_kwargs['stem'] = os.environ.get('BENCH_IMAGENET_STEM', 'conv7')
    model = model_cls(**model_kwargs)
    state = create_train_state(jax.random.PRNGKey(0), model,
                               (1, _IMAGE_SIZE, _IMAGE_SIZE, 3),
                               mesh=mesh, learning_rate=0.1)

    # Amortize per-step Python dispatch: fetch K loader batches,
    # concatenate ON DEVICE (transfer events stay at the per-batch size),
    # and lax.scan runs the K sequential SGD steps in one compiled program.
    # K=1 degrades to the plain per-step trainer.
    scan_k = max(1, int(os.environ.get('BENCH_IMAGENET_SCAN_K', '8')))
    # prefetch=0 stages in the consumer thread (no transfers during compute);
    # >0 overlaps staging with compute via the background thread.
    prefetch = int(os.environ.get('BENCH_IMAGENET_PREFETCH', str(max(2, scan_k))))
    # fence=1 blocks on the loss after each scan group, serializing compute
    # and the next group's transfers.
    fence = os.environ.get('BENCH_IMAGENET_FENCE') == '1'
    # Pass-through to JaxLoader(stage_chunks=); 1 (one put per field) is
    # what docs/tpu_guide.rst prescribes for a directly attached chip.
    stage_chunks = int(os.environ.get('BENCH_STAGE_CHUNKS', '1'))

    # Self-configuring pipeline (ISSUE 4): the adaptive autotuner runs by
    # default; BENCH_IMAGENET_AUTOTUNE=0 pins the hand-tuned knobs.
    autotune_on = os.environ.get('BENCH_IMAGENET_AUTOTUNE', '1') == '1'

    aug = os.environ.get('BENCH_IMAGENET_AUG') == '1'
    if aug:
        # Measure the fused on-device Inception augmentation instead of
        # the bare cast. The key is derived ON DEVICE from the batch's
        # first pixel: a constant key would let XLA constant-fold the RNG
        # and resample coefficients and overstate throughput, while a
        # data-derived key keeps every step's threefry/crop/flip math in
        # the compiled program — the same per-step cost shape as real
        # training's fold_in (never use this for actual training:
        # augmentation must not correlate with the data).
        from petastorm_tpu.ops.augment import imagenet_train_augment

        def normalize(images_u8):
            seed = images_u8[0, 0, 0, 0].astype(jnp.uint32)
            return imagenet_train_augment(images_u8, jax.random.PRNGKey(seed),
                                          out_h=_IMAGE_SIZE,
                                          out_w=_IMAGE_SIZE,
                                          dtype=jnp.float32)
    else:
        def normalize(images_u8):
            # uint8 -> float inside the compiled body: transfers ride h2d
            # as uint8 (4x fewer bytes) and the cast fuses into conv 1.
            return images_u8.astype(jnp.float32) / 255.0

    if scan_k > 1:
        train_step = make_scan_train_step(mesh=mesh, microbatches=scan_k,
                                          preprocess=normalize)
    else:
        inner_step = make_train_step(mesh=mesh)

        @partial(jax.jit, donate_argnums=(0,))
        def train_step(state, images_u8, labels):
            return inner_step(state, normalize(images_u8), labels)

    # Thread pool: the C++ batch decode + parquet read release the GIL, and
    # decoded chunks reach the loader with zero serialization. The decoded
    # RAM cache makes steady-state epochs pure memcpy (multi-epoch training
    # over a dataset that fits host RAM; first epoch pays the decode).
    superbatch = batch * scan_k
    warmup_iters = max(1, -(-warmup_steps // scan_k))
    measure_iters = max(1, -(-measure_steps // scan_k))

    config = {
        'reader': 'make_tensor_reader',
        'reader_pool': 'thread',
        'workers_count': workers,
        'cache_type': 'memory',
        'batch_per_chip': batch // n_devices,
        'global_batch': batch,
        'scan_microbatches': scan_k,
        'superbatch': superbatch,
        'prefetch': prefetch,
        'stage_chunks': stage_chunks,
        'fence_per_group': fence,
        'model': model_name,
        'stem': model_kwargs.get('stem'),
        'warmup_steps': warmup_iters * scan_k,
        'measure_steps': measure_iters * scan_k,
        'native_parquet': os.environ.get('PETASTORM_TPU_NATIVE_PARQUET', 'auto'),
        'native_image': not os.environ.get('PETASTORM_TPU_NO_NATIVE'),
        'on_device_augment': aug,
        'autotune': autotune_on,
    }
    reader = make_tensor_reader(url, schema_fields=['image', 'label'],
                                reader_pool_type='thread', workers_count=workers,
                                num_epochs=None, shuffle_row_groups=True, seed=0,
                                cache_type='memory')

    # Provenance ledger (ISSUE 7): armed with a throwaway dir so the stage
    # profile reports record counts + a replay self-check over real jpegs.
    from petastorm_tpu import lineage as lineage_mod
    ledger_dir = tempfile.mkdtemp(prefix=lineage_mod.TEMP_DIR_PREFIX)
    with reader:
        with JaxLoader(reader, batch, mesh=mesh, prefetch=prefetch,
                       stage_chunks=stage_chunks,
                       autotune=autotune_on,
                       lineage=ledger_dir) as loader:
            it = loader.superbatches(scan_k)
            for _ in range(warmup_iters):
                b = next(it)
                state, metrics = train_step(state, b.image, b.label)
            jax.block_until_ready(metrics['loss'])
            loader.reset_stats()
            t_read0 = dict(reader.stage_timings)
            start = time.perf_counter()
            for _ in range(measure_iters):
                b = next(it)
                state, metrics = train_step(state, b.image, b.label)
                if fence:
                    jax.block_until_ready(metrics['loss'])
            jax.block_until_ready(metrics['loss'])
            elapsed = time.perf_counter() - start
            stats = loader.stats
    # Device-resident steady state (device_cache.py): the decoded dataset
    # lives in HBM, epochs reshuffle on device — zero h2d during training.
    # _sustained_best picks the headline from the two configurations at
    # fold time (with basis/stall/mfu provenance); both ride this child's
    # jitted train step, and the streamed numbers always stay in the JSON.
    hbm_cached = None
    if os.environ.get('BENCH_IMAGENET_DEVICE_CACHE', '1') == '1':
        bare = None
        if aug:
            # Matched in-run baseline for the augmentation-cost claim:
            # the SAME state (copied before donation), cache build, and
            # measurement protocol with the bare uint8 cast — dividing
            # best-slot rates from different grants under different box
            # load would make the cost ratio noise.
            state_copy = jax.tree_util.tree_map(
                lambda x: jnp.array(x) if hasattr(x, 'dtype') else x,
                state)

            def bare_normalize(images_u8):
                return images_u8.astype(jnp.float32) / 255.0

            if scan_k > 1:
                bare_step = make_scan_train_step(
                    mesh=mesh, microbatches=scan_k,
                    preprocess=bare_normalize)
            else:
                bare_inner = make_train_step(mesh=mesh)

                @partial(jax.jit, donate_argnums=(0,))
                def bare_step(state, images_u8, labels):
                    return bare_inner(state, bare_normalize(images_u8),
                                      labels)

            bare = _measure_device_cache(
                jax, url, workers, batch, scan_k, mesh, bare_step,
                state_copy)
        hbm_cached = _measure_device_cache(
            jax, url, workers, batch, scan_k, mesh, train_step, state)
        if bare is not None:
            bare_rate = bare['imagenet_hbm_cached_img_per_sec_per_chip']
            aug_rate = hbm_cached['imagenet_hbm_cached_img_per_sec_per_chip']
            hbm_cached['hbm_cached_bare_img_per_sec_per_chip'] = bare_rate
            hbm_cached['aug_cost_frac'] = round(1 - aug_rate / bare_rate, 4)

    # Per-stage profile over the measure window (VERDICT r2 #1): worker read/
    # decode/cache seconds are cumulative, so delta from the warmup snapshot.
    t_read = stats.get('worker_stage_timings', {})
    stage_profile = {k: round(t_read.get(k, 0) - t_read0.get(k, 0), 4)
                     for k in ('read_s', 'decode_s', 'cache_s')}
    stage_profile['stage_dispatch_s'] = stats['stage_dispatch_s']
    stage_profile['consumer_wait_s'] = stats['wait_s']
    stage_profile['wall_s'] = round(elapsed, 4)
    stage_profile.update(_staging_counters(stats))
    stage_profile.update(_robustness_counters(stats))
    stage_profile['rss_mb'] = _rss_mb()
    stage_profile['rss_peak_mb'] = _peak_rss_mb()
    mem_rec = _mem_governor_summary()
    if mem_rec is not None:
        stage_profile['mem'] = mem_rec
    stage_profile['metrics'] = _metrics_snapshot()
    lineage_rec = _lineage_summary(loader, ledger_dir)
    if lineage_rec is not None:
        stage_profile['lineage'] = lineage_rec
    train_steps = measure_iters * scan_k
    rate = superbatch * measure_iters / elapsed
    # MFU (VERDICT r3 #2): model FLOPs actually retired / chip peak. Uses
    # the published fwd FLOP count x3 (fwd+bwd) — an analytic lower bound
    # (ignores batch norm etc.), the standard convention — against the
    # chip's bf16 peak (an unknown device raises in _peak_bf16_flops). Only
    # meaningful on a chip with a known model; otherwise mfu_note says why
    # it is absent.
    mfu = None
    mfu_note = None
    fwd_flops = _model_fwd_flops(config['model'], config.get('stem'))
    peak = _peak_bf16_flops(jax.devices()[0]) if platform != 'cpu' else None
    if platform == 'cpu':
        mfu_note = 'cpu run: no chip peak to normalize against'
    elif fwd_flops is None:
        mfu_note = 'no published FLOP count for model {!r}'.format(config['model'])
    else:
        mfu = _mfu(fwd_flops, rate / n_devices, peak)
    out = {
        'imagenet_img_per_sec_per_chip': round(rate / n_devices, 2),
        'input_stall_frac': stats['input_stall_frac'],
        'step_time_ms': round(1000 * elapsed / train_steps, 2),
        'n_devices': n_devices,
        'platform': platform,
        'mfu': mfu,
        'mfu_basis': ({'fwd_flops_per_img': fwd_flops,
                       'train_multiplier': _TRAIN_FLOP_MULT,
                       'peak_bf16_flops_per_chip': peak,
                       'stem': config.get('stem'),
                       'device_kind': getattr(jax.devices()[0],
                                              'device_kind', '')}
                      if mfu is not None else mfu_note),
        'stage_profile': stage_profile,
        'staged_GB': round(stats['staged_bytes'] / 1e9, 3),
        'final_loss': round(float(metrics['loss']), 4),
        'bench_config': config,
    }
    autotune_rec = _autotune_summary(stats)
    if autotune_rec is not None:
        out['imagenet_autotune'] = autotune_rec
    out.update(h2d)
    if hbm_cached is not None:
        out.update(hbm_cached)
        # MFU of the HBM-resident steady state: same train step, same
        # analytic FLOP basis, the cached rate instead of the streamed
        # one (rates are per-chip, peak is per-chip: they cancel).
        hbm_rate = hbm_cached.get('imagenet_hbm_cached_img_per_sec_per_chip')
        if fwd_flops is not None and peak is not None and hbm_rate:
            out['hbm_cached_mfu'] = _mfu(fwd_flops, hbm_rate, peak)
        # Dispatch-ceiling gate (ISSUE 17): streamed img/s against the
        # HBM-resident ceiling. On the CPU-forced config "h2d" is a
        # memcpy, so any gap is pure dispatch machinery overhead — the
        # streamed path must hold >= 0.9x of zero-h2d throughput. On a
        # real pod the ratio is reported but not gated (a genuine PCIe
        # wall is the input-bound escape hatch's business, not a
        # regression).
        if hbm_rate:
            streamed_rate = rate / n_devices
            ratio = round(streamed_rate / hbm_rate, 4)
            stage_profile['streamed_vs_hbm_resident'] = {
                'streamed_img_per_sec_per_chip': round(streamed_rate, 2),
                'hbm_resident_img_per_sec_per_chip': round(hbm_rate, 2),
                'ratio': ratio,
                'gate_min_ratio': 0.9,
                'gate_applies': platform == 'cpu',
                'gate_passed': (ratio >= 0.9 if platform == 'cpu'
                                else None),
            }
    print(json.dumps(out))


def _measure_device_cache(jax, url, workers, batch, scan_k, mesh, train_step,
                          state, epochs=6):
    """Steady-state img/s with the decoded dataset resident in HBM
    (``DeviceDatasetCache``): epoch 0 streams-and-caches, measured epochs
    run entirely on device (per-epoch on-device reshuffle, zero h2d)."""
    import jax.numpy as jnp

    # Few-batches-per-epoch configs (multi-chip scales the global batch up)
    # must still accumulate enough batches for >=2 measured superbatches.
    epochs = max(epochs, 2 * scan_k)

    from petastorm_tpu import make_tensor_reader
    from petastorm_tpu.device_cache import DeviceDatasetCache
    from petastorm_tpu.jax_loader import JaxLoader

    reader = make_tensor_reader(url, schema_fields=['image', 'label'],
                                reader_pool_type='thread',
                                workers_count=workers, num_epochs=1, seed=0,
                                cache_type='memory')
    with reader:
        with JaxLoader(reader, batch, mesh=mesh, last_batch='drop') as loader:
            cache = DeviceDatasetCache(loader, shuffle=True, seed=0)
            for _ in cache.epoch(0):
                pass

    concat = jax.jit(lambda *xs: jnp.concatenate(xs))

    def superbatches(first_epoch, n_epochs):
        # Groups carry across epoch boundaries: with few batches per epoch
        # (multi-chip scales the global batch up) one epoch may hold fewer
        # than scan_k batches, and the scan step's superbatch shape must
        # stay fixed regardless.
        group = []
        for ep in range(first_epoch, first_epoch + n_epochs):
            for b in cache.epoch(ep):
                group.append(b)
                if len(group) == scan_k:
                    if scan_k == 1:
                        yield group[0]
                    else:
                        yield group[0]._replace(
                            **{f: concat(*[getattr(p, f) for p in group])
                               for f in group[0]._fields})
                    group = []

    # Warmup compiles the gather/concat path; then measure. ``metrics`` can
    # only be unbound if the cache is empty, which _first_epoch rejects.
    metrics = None
    for sb in superbatches(1, max(1, scan_k)):
        state, metrics = train_step(state, sb.image, sb.label)
        break
    if metrics is None:
        raise RuntimeError('device cache produced no superbatch')
    jax.block_until_ready(metrics['loss'])
    steps = 0
    t0 = time.perf_counter()
    for sb in superbatches(2, epochs):
        state, metrics = train_step(state, sb.image, sb.label)
        steps += scan_k
    jax.block_until_ready(metrics['loss'])
    elapsed = time.perf_counter() - t0
    if not steps:
        raise RuntimeError('device cache produced no measured superbatches')
    n_devices = jax.device_count()
    return {'imagenet_hbm_cached_img_per_sec_per_chip':
                round(batch * steps / elapsed / n_devices, 2),
            'hbm_cached_GB': round(cache.nbytes / 1e9, 3),
            'hbm_cached_epochs_measured': epochs}


def _run_child(name, args, timeout_s, extra_env=None):
    """Run ``bench.py --_child <name> ...`` and return the JSON object it
    printed. A child that times out, exits non-zero or prints no JSON
    fails the whole run (``SystemExit`` with the reason and the end of the
    child's stderr).

    One process for each chip: this parent never imports jax (``main``
    touches only psutil and the reader), and children run one after
    another, so the child is the only process holding libtpu while it
    runs. A parent that touched jax would hold the chip and every child
    would fail or hang."""
    cmd = [sys.executable, os.path.abspath(__file__), '--_child', name] + list(args)
    env = None
    if extra_env:
        env = dict(os.environ)
        env.update(extra_env)
    try:
        proc = subprocess.run(cmd, timeout=timeout_s, capture_output=True,
                              text=True, env=env)
    except subprocess.TimeoutExpired:
        raise SystemExit('bench child {!r} timed out after {}s'.format(
            name, timeout_s)) from None
    if proc.returncode != 0:
        raise SystemExit('bench child {!r} failed rc={}:\n{}'.format(
            name, proc.returncode, (proc.stderr or '').strip()[-4000:]))
    for line in reversed((proc.stdout or '').strip().splitlines()):
        line = line.strip()
        if line.startswith('{'):
            try:
                return json.loads(line)
            except ValueError:
                continue
    raise SystemExit('bench child {!r} printed no JSON'.format(name))


# The multichip child always runs on the virtual 8-device CPU platform
# (it appends --xla_force_host_platform_device_count=8 itself): the
# per-device dispatch mechanics are platform-independent and a real-TPU
# round must not spend chip time re-proving them.
_MULTICHIP_ENV = {'JAX_PLATFORMS': 'cpu'}


def _sustained_best(inet):
    """Best *sustained training* configuration from an imagenet child record:
    ``(rate, basis, mfu, stall)``. Both configurations drive the SAME jitted
    ResNet-50 train step on real data from the same Parquet store; they
    differ only in where the decoded dataset lives between epochs. The
    streamed rate includes host->device transport. The HBM-resident steady
    state (``DeviceDatasetCache``: epoch 0 streams and
    caches, epochs 2+ train entirely on device with on-device reshuffle) is
    the chip-side sustained rate, with zero input stall by construction."""
    if not isinstance(inet, dict):
        return 0, None, None, None
    streamed = inet.get('imagenet_img_per_sec_per_chip') or 0
    hbm = inet.get('imagenet_hbm_cached_img_per_sec_per_chip') or 0
    if hbm > streamed:
        basis = ('hbm_resident_steady_state: DeviceDatasetCache multi-epoch '
                 'training, epochs measured entirely on device; streamed-'
                 'from-host rate on the same step is {} img/s/chip '
                 '(h2d_chunked_GBps={})'.format(
                     streamed, inet.get('h2d_chunked_GBps')))
        hbm_mfu = inet.get('hbm_cached_mfu')
        if hbm_mfu is None and isinstance(inet.get('mfu_basis'), dict):
            # Older records carry the FLOP/peak basis but predate the
            # hbm_cached_mfu key — same formula, the record's own numbers.
            mb = inet['mfu_basis']
            if mb.get('fwd_flops_per_img') and mb.get('peak_bf16_flops_per_chip'):
                hbm_mfu = _mfu(mb['fwd_flops_per_img'], hbm,
                               mb['peak_bf16_flops_per_chip'],
                               mult=mb.get('train_multiplier',
                                           _TRAIN_FLOP_MULT))
        return hbm, basis, hbm_mfu, 0.0
    return (streamed, 'streamed_from_host', inet.get('mfu'),
            inet.get('input_stall_frac'))


def _set_headline(result, inet, source=None):
    """Point the headline keys (metric/value/unit/vs_baseline + provenance)
    at an imagenet child record, choosing its best sustained configuration.

    Headline hygiene: the HBM-resident basis gets a
    DISTINCT metric name (``..._sustained``) plus a machine-checkable
    ``headline_config`` key, so a cross-round diff can never silently
    compare a streamed-from-host number against an HBM-resident one."""
    rate, basis, mfu, stall = _sustained_best(inet)
    hbm_basis = bool(basis) and basis.startswith('hbm_resident')
    result['metric'] = ('imagenet_resnet50_img_per_sec_per_chip_sustained'
                        if hbm_basis
                        else 'imagenet_resnet50_img_per_sec_per_chip')
    result['headline_config'] = ('hbm_resident' if hbm_basis
                                 else 'streamed_from_host')
    result['value'] = rate
    result['unit'] = 'img/s/chip'
    result['vs_baseline'] = round(rate / _NORTH_STAR_IMG_PER_SEC, 3)
    result['headline_basis'] = basis
    result['headline_mfu'] = mfu
    result['headline_stall_frac'] = stall
    result['headline_platform'] = inet.get('platform')
    streamed = inet.get('imagenet_img_per_sec_per_chip')
    if streamed is not None:
        # Both ratios stay visible: the sustained headline above, and the
        # streamed-from-host rate against the same north star; judge them
        # together. headline_-prefixed so they are unambiguously from the
        # SAME run as the headline.
        result['headline_streamed_img_per_sec_per_chip'] = streamed
        result['headline_streamed_vs_baseline'] = round(
            streamed / _NORTH_STAR_IMG_PER_SEC, 3)
    if source:
        result['headline_source'] = source


def _require_tpu(timeout_s=600):
    """The device this run will measure, as jax reports it from a child
    process (the parent stays off jax — see ``_run_child``): ``{'platform',
    'kind', 'count'}``. Exits non-zero unless it is a TPU: a chipless
    ``python bench.py`` measures nothing and prints no rate."""
    probe = ('import json, jax; d = jax.devices(); '
             'print(json.dumps({"platform": d[0].platform, '
             '"kind": d[0].device_kind, "count": len(d)}))')
    try:
        proc = subprocess.run([sys.executable, '-c', probe],
                              timeout=timeout_s, capture_output=True,
                              text=True)
    except subprocess.TimeoutExpired:
        raise SystemExit('bench.py: jax backend did not start within {}s'
                         .format(timeout_s)) from None
    if proc.returncode != 0:
        raise SystemExit('bench.py: jax backend failed to start:\n{}'.format(
            (proc.stderr or '').strip()[-2000:]))
    device = json.loads(proc.stdout.strip().splitlines()[-1])
    if device['platform'] != 'tpu':
        raise SystemExit(
            'bench.py needs a TPU; jax reports platform={!r} ({} x {!r}). '
            'Nothing was measured.'.format(
                device['platform'], device['count'], device['kind']))
    return device


def main():
    _repo_on_path()
    import psutil
    # Floor at 4 even on tiny hosts: parquet reads and the C++ batch decode
    # release the GIL, so extra worker threads overlap I/O with decode even
    # on a single core (1 worker serializes the whole pipeline).
    workers = max(4, min(10, (psutil.cpu_count(logical=True) or 4)))

    if len(sys.argv) >= 3 and sys.argv[1] == '--_child':
        name = sys.argv[2]
        if name == 'staging':
            _child_staging(sys.argv[3], int(sys.argv[4]),
                           sys.argv[5] if len(sys.argv) > 5 else 'thread')
        elif name == 'imagenet':
            _child_imagenet(sys.argv[3], int(sys.argv[4]))
        elif name == 'pipeline':
            cache_tiers = None
            for extra in sys.argv[5:]:
                if extra.startswith('--cache-tiers='):
                    cache_tiers = extra.split('=', 1)[1]
            _child_pipeline(sys.argv[3], int(sys.argv[4]),
                            cache_tiers=cache_tiers)
        elif name == 'multichip':
            _child_multichip(sys.argv[3], int(sys.argv[4]))
        elif name == 'lookup':
            _child_lookup()
        elif name == 'fleet_wire':
            _child_fleet_wire()
        elif name == 'flashattn':
            _child_flashattn()
        elif name == 'lm':
            _child_lm(int(sys.argv[3]) if len(sys.argv) > 3 else workers)
        else:
            raise SystemExit('unknown child {!r}'.format(name))
        return

    # First of all: no chip, no benchmark (and no host-side number printed
    # where a reader might take it for a device metric).
    device = _require_tpu()

    hello_url = _ensure_hello_dataset()
    # Auto-tune the hello pool config. The sweep covers the inline dummy
    # pool (on a 1-CPU host the feeder thread's GIL ping-pong costs ~25%
    # of the per-row path — PROFILE_r04.md; inline ventilation removes it)
    # and thread-pool sizes for multi-core hosts. The sweep only CHOOSES
    # the config; the reported rate is the MEDIAN of 3 fresh runs at that
    # config — a shared host's throughput fluctuates, a single draw would
    # make cross-round comparisons noise, and a max over noisy runs would
    # bias the headline upward.
    swept = [('dummy', 1)] + [('thread', w) for w in sorted({1, 2, workers})]
    sweep_rates = {cfg: _measure_reader(hello_url, cfg[1], pool=cfg[0])
                   for cfg in swept}
    hello_pool, hello_workers = max(sweep_rates, key=sweep_rates.get)
    reps = sorted(_measure_reader(hello_url, hello_workers, pool=hello_pool)
                  for _ in range(3))
    reader_rate = reps[1]
    # Single-draw max over every run at the winning config: the r01/r02
    # methodology (one draw) for cross-round comparability alongside the
    # noise-robust median headline (VERDICT r3 #7).
    single_draw_max = max(reps + [sweep_rates[(hello_pool, hello_workers)]])
    # Decoded-row RAM cache steady state at the same config.
    cached_rate = _measure_reader(hello_url, hello_workers,
                                  cache_type='memory', pool=hello_pool)

    result = {
        'device': device,
        'metric': 'hello_world_samples_per_sec',
        'value': round(reader_rate, 2),
        'unit': 'samples/s',
        'vs_baseline': round(reader_rate / _BASELINE_SAMPLES_PER_SEC, 3),
        # Decoded-row RAM cache (cache_type='memory'): the multi-epoch
        # steady state. Reference-parity headline above stays uncached.
        'hello_world_cached_samples_per_sec': round(cached_rate, 2),
        'hello_world_single_draw_max': round(single_draw_max, 2),
        'hello_config': {'reader_pool': hello_pool,
                         'workers_count': hello_workers,
                         'configs_swept': ['{}-{}'.format(p, w)
                                           for p, w in swept],
                         'sweep_rates': {'{}-{}'.format(p, w): round(r, 1)
                                         for (p, w), r in sweep_rates.items()},
                         'rep_rates': [round(r, 1) for r in reps],
                         'rows': _ROWS, 'warmup': _WARMUP_SAMPLES,
                         'measure': _MEASURE_SAMPLES},
    }

    imagenet_url = _ensure_imagenet_dataset()

    # The staging child rides the same per-row make_reader path the sweep
    # just tuned — reuse its winner rather than the decode-pool floor.
    result.update(_run_child('staging',
                             [hello_url, str(hello_workers), hello_pool],
                             timeout_s=600))

    inet = _run_child('imagenet', [imagenet_url, str(workers)],
                      timeout_s=1800)
    result.update(inet)
    # The north star becomes the headline metric once measured — at the
    # best sustained training configuration the child measured.
    _set_headline(result, inet)
    result['hello_world_samples_per_sec'] = round(reader_rate, 2)
    result['hello_world_vs_reference'] = round(
        reader_rate / _BASELINE_SAMPLES_PER_SEC, 3)

    # Loader-only pipeline capacity (r4 #2) and the Pallas flash-attention
    # certification + timings.
    result['pipeline'] = _run_child('pipeline', [imagenet_url, str(workers)],
                                    timeout_s=900)
    # Multi-device dispatch certification (ISSUE 14): always on the forced
    # 8-device CPU platform — see _MULTICHIP_ENV.
    result['multichip'] = _run_child('multichip',
                                     [imagenet_url, str(workers)],
                                     timeout_s=900, extra_env=_MULTICHIP_ENV)
    # Point-read SLO (ISSUE 15): warm/cold p50/p99 + hit rate through the
    # lookup rpc plane; host-side only, so it never contends for the chip.
    result['lookup'] = _run_child('lookup', [], timeout_s=900,
                                  extra_env={'JAX_PLATFORMS': 'cpu'})
    # Data-plane wire tiers (ISSUE 20): pickle vs arrow-ipc vs shm over
    # the loopback service path; host-side, never contends for the chip.
    result['fleet_wire'] = _run_child('fleet_wire', [], timeout_s=900,
                                      extra_env={'JAX_PLATFORMS': 'cpu'})
    result['flash_attention'] = _run_child('flashattn', [], timeout_s=900)

    _print_result(result)


def _print_result(result):
    """Emit the full JSON, then a compact summary as the LAST stdout line —
    the driver archives only a stdout tail."""
    print(json.dumps(result))
    summary = {'metric': result.get('metric'), 'value': result.get('value'),
               'unit': result.get('unit'),
               'vs_baseline': result.get('vs_baseline'),
               'device': result.get('device')}
    # mfu/stall/platform must come from the SAME run AND configuration as
    # the headline value — _set_headline records them alongside it.
    if 'headline_basis' in result:
        summary['mfu'] = result.get('headline_mfu')
        summary['input_stall_frac'] = result.get('headline_stall_frac')
        summary['platform'] = result.get('headline_platform')
        summary['basis'] = (result['headline_basis'] or '').split(':')[0]
    else:
        summary['mfu'] = result.get('mfu')
        summary['input_stall_frac'] = result.get('input_stall_frac')
        summary['platform'] = result.get('platform')
    sys.stdout.flush()
    print('BENCH_SUMMARY ' + json.dumps(summary), flush=True)


if __name__ == '__main__':
    main()
