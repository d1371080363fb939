#!/usr/bin/env python
"""First contact with the chip: drive the main path once and check what lands.

``python chip_smoke.py`` needs a TPU and refuses to run without one. It

* writes an ImageNet-shaped jpeg store and a token store from a seed
  (``etl.writer.write_dataset``),
* proves the bytes: for more batches than three arena pools hold, a per-row
  checksum computed ON THE DEVICE over the staged image and id equals the
  checksum of the same row from an independent host-only read of the store,
  and every batch field sits on every chip of the ``{'data': n}`` mesh,
* trains ``ResNet50(1000)`` at 224x224 and 128 images per chip, and the
  42M-parameter ``TransformerLM`` with ``attention='flash'``, each for a few
  steps off ``make_tensor_reader -> JaxLoader`` through
  ``create_train_state`` / ``make_train_step`` / ``make_scan_train_step``,
* runs an epoch out of ``DeviceDatasetCache`` and compares it with the
  streamed one,
* compiles the Pallas kernels (flash attention forward and backward, the
  grouped product, the routed experts' two layouts, a sub-layer between its
  hyper-connection maps, Kimi
  delta attention's rule with every gate at its bound, ``normalize_images``)
  and checks them against their XLA references.

Nothing in it catches a failure to go on: the first phase that fails ends
the run with a non-zero exit code. It reports compile seconds per program,
wall time per phase and peak HBM per device; any rate it prints is
informational, because this script defines no metric. The last line of its
standard output is ``{"ok": true, "device": {...}}``.

One process uses the chip: run nothing else that touches jax beside it.

``JAX_PLATFORMS=cpu python chip_smoke.py --cpu-tiny`` is the rehearsal for
the sandbox: the same phases at toy sizes, the Pallas kernels in interpret
mode, ``platform=cpu`` on every line. It is never what the script does by
default and its last line is not the chip's result line.
"""

import argparse
import collections
import contextlib
import importlib.metadata
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

# What runs on the chip: full width, the BASELINE.json image configuration
# and the repo's LM width. store_batches x proof_epochs is how many batches
# the byte proof checks (it must exceed three arena pools).
FULL = {
    'image_size': 224, 'per_chip': 128, 'classes': 1000, 'resnet': 'ResNet50',
    'store_batches': 6, 'proof_epochs': 4, 'train_steps': 3, 'scan_k': 2,
    'lm': {'vocab': 32768, 'd_model': 512, 'layers': 8, 'heads': 8,
           'seq': 1024, 'per_chip': 8, 'steps': 3, 'store_batches': 4},
    # (name, dtype, B, T, H, D): the LM's shape; a long sequence at the
    # default (512, 1024) bf16 blocks; f32 at a T no block divides.
    # the hybrid LM at the published head widths (96-wide keys, 192-wide
    # values, 128-wide attention heads), four of eight heads held
    'hybrid': {'vocab': 4096, 'd_model': 1024, 'd_ff': 2048, 'heads': 4,
               'published': 8, 'key': 96, 'value': 192, 'seq': 1024,
               'chunk': 64},
    'flash': [('bf16-lm-shape', 'bfloat16', 8, 1024, 8, 64),
              ('bf16-T8192', 'bfloat16', 1, 8192, 8, 64),
              ('f32-ragged-T1000', 'float32', 2, 1000, 4, 64)],
    'normalize': [(128, 224, 224, 3), (100, 300, 300, 3)],
    # xing4.tokens4k's first product: pairs of 4,096 tokens x top 4 against
    # eight experts, tiles of 128 rows (tokens, top_k, experts, k, n, tile)
    'grouped': (4096, 4, 8, 3584, 2048, 128),
    # ling3.tokens8k's routed part: 8,192 tokens, top 8, 8 of 512 experts
    # held (tokens, top_k, held, published, d, f, tile)
    'routed': (8192, 8, 8, 512, 2560, 768, 128),
    # xing4.tokens4k's residual: 4,096 tokens of four 3584-wide streams
    'streams': (4096, 4, 3584),
    # ling3.tokens8k's rule: 8,192 tokens, 32 heads of 128, chunks of 64 in
    # sub-blocks of 16 (tokens, heads, width, chunk, sub-block)
    'kda': (8192, 32, 128, 64, 16),
}
TINY = {
    'image_size': 32, 'per_chip': 4, 'classes': 10, 'resnet': 'ResNetTiny',
    'store_batches': 6, 'proof_epochs': 4, 'train_steps': 2, 'scan_k': 2,
    'lm': {'vocab': 256, 'd_model': 32, 'layers': 1, 'heads': 2,
           'seq': 64, 'per_chip': 2, 'steps': 2, 'store_batches': 4},
    'hybrid': {'vocab': 128, 'd_model': 32, 'd_ff': 64, 'heads': 2,
               'published': 4, 'key': 8, 'value': 16, 'seq': 48, 'chunk': 16},
    'flash': [('bf16-small', 'bfloat16', 2, 64, 2, 16),
              ('bf16-multiblock', 'bfloat16', 1, 256, 2, 16),
              ('f32-ragged-T50', 'float32', 2, 50, 2, 16)],
    'normalize': [(8, 16, 16, 3), (5, 10, 10, 3)],
    'grouped': (24, 2, 3, 32, 128, 8),
    'routed': (48, 4, 2, 32, 32, 16, 8),
    'streams': (48, 4, 128),
    'kda': (40, 2, 16, 16, 4),
}

# Max |kernel - reference| over max |reference|, forward and input gradients,
# against dense attention computed in float32 at full matmul precision. The
# kernel's matmuls run on the MXU with bf16 operands whatever the input dtype
# (Mosaic's default contract precision, like XLA's default for an f32 dot on
# a TPU), so 0.2% / 0.5% — what the kernel differed by from dense attention
# at that same reduced precision when it was first certified — is widened
# by the rounding the exact reference does not share: half a bf16 ulp (2**-8
# of a value) on the way out, and once more on the way into the backward.
FLASH_FWD_TOL = 2e-3 + 2 ** -8
FLASH_GRAD_TOL = 5e-3 + 2 ** -7
# Both routes accumulate a product in float32 and round it once to bf16: they
# differ by the order of the sum, an ulp of bf16 at the largest value.
GROUPED_TOL = 2 ** -7
# The kernels round the streams' gradient once where the jax.numpy
# formulation rounds it in three places (and dPhi once more): two bf16 ulps.
STREAMS_TOL = 2 ** -6


# Both routes run the same chunk bodies in bfloat16 (the kernels through
# Mosaic, the jax.numpy form through XLA): an ulp of bf16 at the largest value.
KDA_TOL = 2 ** -7


class Run(object):
    """What the phases share: the mode, the mesh, the stores, the record."""

    def __init__(self, cfg, interpret, platform):
        self.cfg = cfg
        self.interpret = interpret
        self.platform = platform
        self.mesh = None
        self.n = None
        self.workdir = None
        self.image_url = None
        self.token_url = None
        self.host_checksums = None      # id -> checksum, host-only read
        self.checksum = None            # jitted device checksum
        self.phase_s = collections.OrderedDict()
        self.compile_s = collections.OrderedDict()
        self.cache_events = collections.Counter()

    def say(self, msg=''):
        for line in str(msg).splitlines() or ['']:
            print('[platform={}] {}'.format(self.platform, line), flush=True)

    @contextlib.contextmanager
    def phase(self, name):
        self.say('== {}'.format(name))
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.phase_s[name] = time.perf_counter() - t0
        self.say('== {} ok in {:.1f}s'.format(name, self.phase_s[name]))

    def compile(self, name, jitted, *args):
        """AOT-compile ``jitted`` for ``args`` and record the seconds, so
        compile time is reported apart from step time."""
        t0 = time.perf_counter()
        compiled = jitted.lower(*args).compile()
        self.compile_s[name] = time.perf_counter() - t0
        self.say('compiled {} in {:.2f}s'.format(name, self.compile_s[name]))
        return compiled


def _package_version(name):
    try:
        return importlib.metadata.version(name)
    except importlib.metadata.PackageNotFoundError:
        return 'not installed'


def _cache_entries(path):
    return len(os.listdir(path)) if os.path.isdir(path) else 0


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def phase_environment(run):
    """Native libraries (the Python decode fallback is a several times
    slower host that only says so at logger.info), the staging alias
    probe, and that block_until_ready waits for a transfer."""
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.native import build, image, parquet, pinned, shm_ring
    from petastorm_tpu.staging import staging_aliases_host

    available = {'image': image.available(),
                 'parquet': parquet.is_available(),
                 'pinned': pinned.available(),
                 'shm_ring': shm_ring.available()}
    run.say('native libraries available: {}'.format(available))
    for name, rec in sorted(build.build_report().items()):
        run.say('native {}: source_hash={} build_or_wait_s={} {}'.format(
            name, rec['source_hash'], rec['build_or_wait_s'], rec['path']))
    assert available['image'] and available['parquet'], (
        'native decode/parquet library missing: {}'.format(available))

    aliases = staging_aliases_host(jax)
    run.say('staging_aliases_host={}'.format(aliases))
    if run.platform == 'tpu':
        assert aliases is False, 'a TPU device_put must not alias host memory'

    # Does block_until_ready wait for the bytes? Pull one reduced word back
    # after it: if the transfer were still in flight, that would cost the
    # rest of the transfer, not what it costs from a resident array.
    nbytes = (256 << 20) if run.platform == 'tpu' else (8 << 20)
    ssum = jax.jit(lambda a: jnp.sum(a, dtype=jnp.uint32))
    buf = np.ones(nbytes, np.uint8)
    resident = jax.block_until_ready(jax.device_put(buf))
    int(ssum(resident))                       # compile + warm
    t0 = time.perf_counter()
    assert int(ssum(resident)) == nbytes
    pull_resident_s = time.perf_counter() - t0
    buf[:] = 2
    t0 = time.perf_counter()
    fresh = jax.block_until_ready(jax.device_put(buf))
    ready_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    total = int(ssum(fresh))
    pull_fresh_s = time.perf_counter() - t0
    run.say('block_until_ready: put+ready of {} MiB {:.1f} ms; byte pull '
            'after it {:.1f} ms, from a resident array {:.1f} ms '
            '(informational)'.format(nbytes >> 20, ready_s * 1e3,
                                     pull_fresh_s * 1e3,
                                     pull_resident_s * 1e3))
    assert total == 2 * nbytes, 'device saw stale bytes: {}'.format(total)
    assert pull_fresh_s <= 3 * pull_resident_s + 0.02, (
        'block_until_ready returned before the transfer landed')


def _synthetic_image(rng, size):
    """Photo-like synthetic image (low-frequency field plus mild noise):
    compresses and decodes like a photo, unlike white noise."""
    cells = max(1, size // 16)
    low = rng.integers(0, 255, (cells, cells, 3), dtype=np.uint8)
    img = np.kron(low, np.ones((size // cells, size // cells, 1), np.uint8))
    noise = rng.integers(0, 24, (size, size, 3), dtype=np.uint8)
    return np.clip(img.astype(np.int16) + noise - 12, 0, 255).astype(np.uint8)


def phase_write_stores(run):
    from petastorm_tpu.codecs import (CompressedImageCodec, NdarrayCodec,
                                      ScalarCodec)
    from petastorm_tpu.etl.writer import write_dataset
    from petastorm_tpu.unischema import Unischema, UnischemaField

    cfg, lm = run.cfg, run.cfg['lm']
    size = cfg['image_size']
    batch = cfg['per_chip'] * run.n
    rows = batch * cfg['store_batches']
    schema = Unischema('ChipSmokeImages', [
        UnischemaField('id', np.int64, (), ScalarCodec(np.int64), False),
        UnischemaField('image', np.uint8, (size, size, 3),
                       CompressedImageCodec('jpeg', 90), False),
        UnischemaField('label', np.int64, (), ScalarCodec(np.int64), False)])
    rng = np.random.default_rng(7)
    run.image_url = 'file://' + os.path.join(run.workdir, 'images')
    # Row groups of one global batch: a batch then lies inside one decoded
    # chunk (the loader's block fast path).
    write_dataset(run.image_url, schema,
                  ({'id': i, 'image': _synthetic_image(rng, size),
                    'label': int(rng.integers(0, cfg['classes']))}
                   for i in range(rows)), rows_per_row_group=batch)
    run.say('image store: {} rows of {}x{}x3 jpeg, row groups of {}'.format(
        rows, size, size, batch))

    lm_batch = lm['per_chip'] * run.n
    lm_rows = lm_batch * lm['store_batches']
    tokens = Unischema('ChipSmokeTokens', [
        UnischemaField('tokens', np.int32, (lm['seq'] + 1,), NdarrayCodec(),
                       False)])
    rng = np.random.default_rng(11)
    run.token_url = 'file://' + os.path.join(run.workdir, 'tokens')
    write_dataset(run.token_url, tokens,
                  ({'tokens': rng.integers(0, lm['vocab'], lm['seq'] + 1,
                                           dtype=np.int32)}
                   for _ in range(lm_rows)), rows_per_row_group=lm_batch)
    run.say('token store: {} rows of {} int32 tokens'.format(
        lm_rows, lm['seq'] + 1))


def _checksum_weights(image_shape):
    """Per-position weights: a row's checksum changes when any byte of the
    image changes or two bytes swap places."""
    count = int(np.prod(image_shape))
    return (np.arange(count, dtype=np.uint32) % 65521 + 1).reshape(image_shape)


def _host_checksum(images, ids, weights):
    # uint32 arithmetic wraps modulo 2**32 on the host and on the device
    # alike, and modular sums do not depend on the order of reduction.
    pixel = (images.astype(np.uint32) * weights).reshape(
        len(images), -1).sum(axis=1, dtype=np.uint32)
    return pixel + ids.astype(np.uint32) * np.uint32(2654435761)


def phase_host_reference(run):
    """The independent read: the per-row reader, host only — no arena, no
    staging engine, no device."""
    from petastorm_tpu import make_reader

    size = run.cfg['image_size']
    weights = _checksum_weights((size, size, 3))
    sums = {}
    images, ids = [], []

    def flush():
        for i, c in zip(ids, _host_checksum(np.stack(images),
                                            np.asarray(ids), weights)):
            sums[int(i)] = int(c)
        del images[:], ids[:]

    with make_reader(run.image_url, schema_fields=['id', 'image'],
                     reader_pool_type='thread', workers_count=_workers(),
                     num_epochs=1, shuffle_row_groups=False) as reader:
        for row in reader:
            images.append(row.image)
            ids.append(row.id)
            if len(ids) == 256:
                flush()
    if ids:
        flush()
    run.host_checksums = sums
    run.say('host-only read: {} rows checksummed'.format(len(sums)))

    import jax
    import jax.numpy as jnp
    device_weights = jnp.asarray(weights)

    def device_checksum(images, ids):
        pixel = jnp.sum((images.astype(jnp.uint32) * device_weights).reshape(
            images.shape[0], -1), axis=1, dtype=jnp.uint32)
        return pixel + ids.astype(jnp.uint32) * jnp.uint32(2654435761)

    run.checksum = jax.jit(device_checksum)


def _workers():
    return max(2, min(10, os.cpu_count() or 2))


def _assert_placement(run, batch):
    """Every field of a staged batch holds one shard on each chip of the
    mesh, in the mesh's order: placement asserted, not trusted."""
    mesh_devices = list(run.mesh.devices.flat)
    for name in batch._fields:
        array = getattr(batch, name)
        shards = sorted(array.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        devices = [s.device for s in shards]
        assert len(set(devices)) == run.n, (
            '{}: {} shards on {} distinct devices, mesh has {}'.format(
                name, len(shards), len(set(devices)), run.n))
        assert devices == mesh_devices, (
            '{}: shard order {} is not the mesh order {}'.format(
                name, devices, mesh_devices))
        rows = array.shape[0] // run.n
        for k, shard in enumerate(shards):
            assert shard.data.shape[0] == rows, (name, shard.data.shape)
            assert (shard.index[0].start or 0) == k * rows, (name, shard.index)


def _compare_with_host(run, pairs, what, copies):
    """``pairs`` is (ids, checksums) per batch, still on the device."""
    seen = collections.Counter()
    bad = []
    for ids, sums in pairs:
        for i, c in zip(np.asarray(ids).tolist(), np.asarray(sums).tolist()):
            seen[i] += 1
            if run.host_checksums[i] != c:
                bad.append(i)
    assert not bad, '{}: {} row(s) differ from the host read, e.g. ids {}'.format(
        what, len(bad), bad[:8])
    assert set(seen) == set(run.host_checksums), (
        '{}: {} of {} ids delivered'.format(what, len(seen),
                                            len(run.host_checksums)))
    assert set(seen.values()) == {copies}, (
        '{}: ids delivered {} times, expected {}'.format(
            what, sorted(set(seen.values())), copies))
    return sum(seen.values())


def phase_byte_proof(run):
    """Arena recycled under an in-flight transfer, or a shard on the wrong
    chip, shows as a wrong checksum here — not as an exception anywhere."""
    from petastorm_tpu import make_tensor_reader
    from petastorm_tpu.jax_loader import JaxLoader

    cfg = run.cfg
    batch = cfg['per_chip'] * run.n
    epochs = cfg['proof_epochs']
    reader = make_tensor_reader(
        run.image_url, schema_fields=['id', 'image', 'label'],
        reader_pool_type='thread', workers_count=_workers(),
        num_epochs=epochs, shuffle_row_groups=True, seed=0,
        cache_type='memory')
    pairs = []
    with reader:
        # pinned_arenas=True: mlocked slabs are the arenas a DMA engine
        # reads straight out of, so they are the ones to prove.
        with JaxLoader(reader, batch, mesh=run.mesh,
                       pinned_arenas=True) as loader:
            for b in loader:
                _assert_placement(run, b)
                # Not fetched yet: keep the pipeline running ahead so that
                # arenas recycle while transfers are in flight.
                pairs.append((b.id, run.checksum(b.image, b.id)))
            stats = loader.stats
    rows = _compare_with_host(run, pairs, 'byte proof', copies=epochs)
    batches = len(pairs)
    run.say('{} rows in {} batches: every device checksum equals the host '
            'read'.format(rows, batches))
    run.say('staging tiers (fields staged): {}'.format(stats['stage_tiers']))
    run.say('n_devices={} shards_put={} shards_donated={} ready_wait_s={} '
            'device_ready_wait_s={}'.format(
                stats['n_devices'], stats['shards_put'],
                stats['shards_donated'], stats['ready_wait_s'],
                stats['device_ready_wait_s']))
    run.say('arenas: depth={} allocated={} alloc={} reuse={} pinned={} '
            'pinned_mode={} mlocked={} of {} pinned_bytes={}'.format(
                stats['arena_depth'], stats['arena_allocated'],
                stats['arena_alloc'], stats['arena_reuse'],
                stats['arena_pinned'], stats['arena_pinned_mode'],
                stats['arena_pinned_locked'], stats['arena_allocated'],
                stats['arena_pinned_bytes']))
    assert stats['n_devices'] == run.n, stats['n_devices']
    assert stats['shards_put'] >= batches * run.n, stats['shards_put']
    assert batches >= 3 * stats['arena_depth'], (
        '{} batches do not cover three pools of {}'.format(
            batches, stats['arena_depth']))
    if run.platform == 'tpu':
        # A copying backend collates every batch into a recycled arena.
        assert stats['arena_reuse'] >= 2 * stats['arena_depth'], (
            'arenas were not recycled: {}'.format(stats))
    else:
        run.say('the {} backend aliases host memory (DLPack): batches were '
                'staged as chunk views and no arena was recycled — only '
                'the chip run proves the recycling'.format(run.platform))
    carried = set(stats['stage_tiers'])
    assert carried <= {'inline-batched', 'streamed-batched'}, (
        'a field left the per-device batched tiers: {}'.format(carried))


def _train(run, tag, loader, state, prepare, fields, steps, scan_k):
    """A few ``make_train_step`` steps, then one ``make_scan_train_step``
    call of ``scan_k`` micro-steps, each ended by block_until_ready."""
    import jax

    from petastorm_tpu.models.train import (make_scan_train_step,
                                            make_train_step)

    step = None
    for i in range(steps):
        b = next(loader)
        x, y = prepare(*[getattr(b, f) for f in fields])
        if step is None:
            step = run.compile(tag + '.train_step',
                               make_train_step(mesh=run.mesh), state, x, y)
        t0 = time.perf_counter()
        state, metrics = step(state, x, y)
        loss = float(jax.block_until_ready(metrics['loss']))
        run.say('{} step {}: loss {:.4f}, {:.1f} ms (informational)'.format(
            tag, i, loss, (time.perf_counter() - t0) * 1e3))
        assert np.isfinite(loss), '{} step {}: loss {}'.format(tag, i, loss)

    # The scanned trainer applies `preprocess` to the images only; the LM's
    # input/target split is a slice of one field, done before the call.
    sb = next(loader.superbatches(scan_k))
    x, y = prepare(*[getattr(sb, f) for f in fields])
    scan = run.compile(tag + '.scan_train_step[k={}]'.format(scan_k),
                       make_scan_train_step(mesh=run.mesh,
                                            microbatches=scan_k),
                       state, x, y)
    t0 = time.perf_counter()
    state, metrics = scan(state, x, y)
    loss = float(jax.block_until_ready(metrics['loss']))
    run.say('{} scan of {} steps: mean loss {:.4f}, {:.1f} ms '
            '(informational)'.format(tag, scan_k, loss,
                                     (time.perf_counter() - t0) * 1e3))
    assert np.isfinite(loss), '{} scan: loss {}'.format(tag, loss)
    return state


def phase_resnet(run):
    import jax
    import jax.numpy as jnp

    from petastorm_tpu import make_tensor_reader
    from petastorm_tpu.jax_loader import JaxLoader
    from petastorm_tpu.models import resnet
    from petastorm_tpu.models.train import create_train_state

    cfg = run.cfg
    size = cfg['image_size']
    batch = cfg['per_chip'] * run.n
    model = getattr(resnet, cfg['resnet'])(num_classes=cfg['classes'])
    state = create_train_state(jax.random.PRNGKey(0), model,
                               (1, size, size, 3), mesh=run.mesh,
                               learning_rate=0.1)
    params = sum(p.size for p in jax.tree_util.tree_leaves(state.params))
    run.say('{}: {:.1f}M parameters, global batch {} ({} per chip), '
            '{}x{}x3 uint8'.format(cfg['resnet'], params / 1e6, batch,
                                   cfg['per_chip'], size, size))
    # uint8 rides the transfer; the cast runs on the device.
    prepare = jax.jit(lambda images, labels:
                      (images.astype(jnp.float32) / 255.0, labels))
    reader = make_tensor_reader(
        run.image_url, schema_fields=['image', 'label'],
        reader_pool_type='thread', workers_count=_workers(),
        num_epochs=None, shuffle_row_groups=True, seed=0,
        cache_type='memory')
    with reader:
        with JaxLoader(reader, batch, mesh=run.mesh) as loader:
            _train(run, cfg['resnet'], loader, state, prepare,
                   ('image', 'label'), cfg['train_steps'], cfg['scan_k'])
            stats = loader.stats
    run.say('loader: tiers={} arena_pinned={} input_stall_frac={} '
            '(informational)'.format(stats['stage_tiers'],
                                     stats['arena_pinned'],
                                     stats['input_stall_frac']))


def phase_lm(run):
    import jax
    import optax

    from petastorm_tpu import make_tensor_reader
    from petastorm_tpu.jax_loader import JaxLoader
    from petastorm_tpu.models import TransformerLM
    from petastorm_tpu.models.train import create_train_state

    lm = run.cfg['lm']
    batch = lm['per_chip'] * run.n
    attention = 'flash:interpret' if run.interpret else 'flash'
    model = TransformerLM(vocab_size=lm['vocab'], d_model=lm['d_model'],
                          num_heads=lm['heads'], num_layers=lm['layers'],
                          max_len=lm['seq'], attention=attention,
                          mesh=run.mesh)
    state = create_train_state(
        jax.random.PRNGKey(0), model, None, mesh=run.mesh,
        tx=optax.sgd(0.01, momentum=0.9),
        example_input=np.zeros((1, lm['seq']), np.int32))
    params = sum(p.size for p in jax.tree_util.tree_leaves(state.params))
    run.say('TransformerLM: {:.1f}M parameters, vocab {} d={} layers={} '
            'heads={} T={} attention={!r}, global batch {}'.format(
                params / 1e6, lm['vocab'], lm['d_model'], lm['layers'],
                lm['heads'], lm['seq'], attention, batch))
    # Next-token prediction: inputs and targets are one row shifted by one.
    prepare = jax.jit(lambda tokens: (tokens[:, :-1], tokens[:, 1:]))
    reader = make_tensor_reader(
        run.token_url, schema_fields=['tokens'], reader_pool_type='thread',
        workers_count=_workers(), num_epochs=None, shuffle_row_groups=True,
        seed=0, cache_type='memory')
    with reader:
        with JaxLoader(reader, batch, mesh=run.mesh) as loader:
            _train(run, 'TransformerLM', loader, state, prepare, ('tokens',),
                   lm['steps'], run.cfg['scan_k'])
            stats = loader.stats
    run.say('loader: tiers={} input_stall_frac={} (informational)'.format(
        stats['stage_tiers'], stats['input_stall_frac']))


def phase_hybrid(run):
    """One train step of ``HybridLM`` (a gated delta-rule layer and a
    full-attention layer, both recomputed in the backward pass) through the
    compiled Pallas kernels, against the same step through plain XLA (the
    rule chunked in ``jax.numpy``, dense attention): a Mosaic lowering
    failure of ``gdn`` on a new runtime shows here."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec

    from petastorm_tpu.models import HybridLM
    from petastorm_tpu.models.train import TrainState, make_train_step

    h = run.cfg['hybrid']
    batch = run.n

    def model(linear_attention, attention):
        return HybridLM(
            vocab_size=h['vocab'], d_model=h['d_model'], d_ff=h['d_ff'],
            layer_types=('linear_attention', 'full_attention'),
            heads_held=h['heads'], heads_published=h['published'],
            key_dim=h['key'], value_dim=h['value'], chunk=h['chunk'],
            attention=attention, linear_attention=linear_attention,
            remat=True, mesh=run.mesh)

    tokens = jax.device_put(
        np.random.default_rng(0).integers(
            0, h['vocab'], (batch, h['seq'] + 1), dtype=np.int32),
        NamedSharding(run.mesh, PartitionSpec('data')))
    x, y = tokens[:, :-1], tokens[:, 1:]
    suffix = ':interpret' if run.interpret else ''
    kernels, plain = model('pallas' + suffix, 'flash' + suffix), \
        model('chunked', 'dense')
    params = jax.jit(plain.init)(jax.random.PRNGKey(0), x)['params']
    run.say('HybridLM: {:.1f}M parameters, d={} heads {} of {} ({}/{}-wide '
            'linear, {}-wide full) T={} chunk={}, global batch {}'.format(
                sum(p.size for p in jax.tree_util.tree_leaves(params)) / 1e6,
                h['d_model'], h['heads'], h['published'], h['key'],
                h['value'], h['d_model'] // h['published'], h['seq'],
                h['chunk'], batch))
    losses, moved = {}, {}
    for name, m in (('kernels', kernels), ('plain', plain)):
        state = TrainState.create(apply_fn=m.apply, params=params,
                                  tx=optax.sgd(0.1))
        state = jax.device_put(state, NamedSharding(run.mesh, PartitionSpec()))
        step = make_train_step(mesh=run.mesh)
        if name == 'kernels':
            step = run.compile('HybridLM train_step', step, state, x, y)
        new, metrics = step(jax.tree_util.tree_map(jnp.copy, state), x, y)
        losses[name] = float(metrics['loss'])
        moved[name] = new.params['head']['kernel']
    gap = abs(losses['kernels'] - losses['plain']) / abs(losses['plain'])
    update = _rel_err(moved['kernels'] - params['head']['kernel'],
                      moved['plain'] - params['head']['kernel'])
    run.say('HybridLM step: loss {:.5f} through the kernels, {:.5f} through '
            'plain XLA (gap {:.3%}); the head moved alike to {:.3%}'.format(
                losses['kernels'], losses['plain'], gap, update))
    assert np.isfinite(losses['kernels']) and gap < 2e-3, losses
    # both sides multiply in bfloat16; they differ by the order of the sums
    assert update < 0.05, update


def phase_device_cache(run):
    """Epoch 0 streams and caches, epoch 1 comes out of HBM: the same rows,
    byte for byte. Then every chip must hold its share of what is resident."""
    import jax

    from petastorm_tpu import device_cache, make_tensor_reader
    from petastorm_tpu.jax_loader import JaxLoader

    batch = run.cfg['per_chip'] * run.n
    reader = make_tensor_reader(
        run.image_url, schema_fields=['id', 'image', 'label'],
        reader_pool_type='thread', workers_count=_workers(), num_epochs=1,
        shuffle_row_groups=True, seed=0)
    streamed, resident = [], []
    with reader:
        with JaxLoader(reader, batch, mesh=run.mesh,
                       last_batch='drop') as loader:
            # No max_bytes: the budget must come from the device's own
            # memory_stats()['bytes_limit'] (an error on a TPU without it).
            cache = device_cache.DeviceDatasetCache(loader, shuffle=True,
                                                    seed=0)
            for b in cache.epoch(0):
                streamed.append((b.id, run.checksum(b.image, b.id)))
    for b in cache.epoch(1):
        _assert_placement(run, b)
        resident.append((b.id, run.checksum(b.image, b.id)))
    _compare_with_host(run, streamed, 'device cache epoch 0', copies=1)
    rows = _compare_with_host(run, resident, 'device cache epoch 1', copies=1)
    stats = cache.stats()
    run.say('epoch 1 out of HBM: {} rows, same ids and bytes as the host '
            'read'.format(rows))
    run.say('device cache: {}'.format(stats))
    assert stats['materialized'] and stats['hits'] >= len(resident), stats
    assert stats['cached_batches'] == stats['total_batches'] == len(streamed)

    limits = [(d.memory_stats() or {}).get('bytes_limit')
              for d in jax.local_devices()]
    if run.platform == 'tpu':
        assert all(limits), 'a TPU reported no bytes_limit: {}'.format(limits)
        assert stats['max_bytes_per_device'] == int(
            limits[0] * device_cache._DEFAULT_HBM_FRACTION), (
                stats['max_bytes_per_device'], limits[0])
        in_use = [d.memory_stats()['bytes_in_use']
                  for d in jax.local_devices()]
        run.say('bytes_in_use per device with the cache resident: {}'.format(
            in_use))
        assert min(in_use) > 0, 'a chip holds nothing: {}'.format(in_use)
        assert max(in_use) <= 2 * min(in_use), (
            'HBM use is not balanced across chips: {}'.format(in_use))
    else:
        run.say('no memory_stats() on platform {}: HBM balance not '
                'checked'.format(run.platform))
    cache.clear()


def _rel_err(got, want):
    import jax.numpy as jnp
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _dense_reference(q, k, v):
    """``dense_attention`` in float32 at full matmul precision, one head at
    a time so the [T, T] scores of a long sequence fit beside the rest."""
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.models.attention import dense_attention

    def per_head(x):        # [B, T, H, D] -> [H, B, T, 1, D]
        return jnp.moveaxis(x.astype(jnp.float32), 2, 0)[:, :, :, None, :]

    with jax.default_matmul_precision('highest'):
        out = jax.lax.map(
            lambda qkv: dense_attention(*qkv, causal=True),
            (per_head(q), per_head(k), per_head(v)))
    return jnp.moveaxis(out[:, :, :, 0, :], 0, 2)


def _grouped_product_check(run, assert_mosaic):
    """The Pallas grouped product of ``ops.grouped_matmul`` against
    ``jax.lax.ragged_dot`` at the shape a dropless expert layer runs it:
    uneven groups laid out in whole tiles (one group empty, one of a single
    pair), forward and both gradients, bf16."""
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.ops.grouped_matmul import (aligned_layout,
                                                  grouped_matmul)

    tokens, top_k, experts, k, n, tile = run.cfg['grouped']
    pairs = tokens * top_k
    # the cell's expectation is pairs / 8 an expert; here the first expert
    # gets nothing, the second one pair, the rest uneven shares of a
    # sixteenth of the pairs each (what 8 of 64 experts are sent)
    rng = np.random.default_rng(0)
    counts = rng.multinomial(pairs // 8, np.ones(experts - 2) / (experts - 2))
    counts = jnp.asarray([0, 1] + counts.tolist(), jnp.int32)
    sizes, _ = aligned_layout(counts, tile)
    rows = pairs + experts * tile
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(keys[0], (rows, k), jnp.bfloat16)
    w = (jax.random.normal(keys[1], (experts, k, n), jnp.float32)
         / np.sqrt(k)).astype(jnp.bfloat16)
    c = jax.random.normal(keys[2], (rows, n), jnp.bfloat16)
    impl = 'pallas:interpret' if run.interpret else 'pallas'

    def product(impl):
        def loss(x, w):
            y = grouped_matmul(x, w, sizes, tile_m=tile, impl=impl)
            return jnp.sum((y * c).astype(jnp.float32)), y
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))

    kernel, plain = product(impl), product('ragged_dot')
    assert_mosaic('grouped', kernel.lower(x, w))
    (_, y), grads = run.compile('grouped.fwd+bwd', kernel, x, w)(x, w)
    (_, want), want_grads = plain(x, w)
    fwd_err = _rel_err(y, want)
    grad_err = max(_rel_err(g, t) for g, t in zip(grads, want_grads))
    run.say('grouped product [{} rows x {} -> {} by {} experts, groups {} in '
            'tiles of {}, bf16]: fwd err {:.4%}, grad err {:.4%} (tol {:.2%}) '
            'against ragged_dot'.format(rows, k, n, experts, counts.tolist(),
                                        tile, fwd_err, grad_err,
                                        GROUPED_TOL))
    ok = fwd_err <= GROUPED_TOL and grad_err <= GROUPED_TOL and not bool(
        jnp.any(y[int(sizes.sum()):]))
    return [] if ok else ['grouped']


def _routed_layouts_check(run, assert_mosaic):
    """The held experts' part of a routed layer (``models.moe.
    routed_experts``) under the rows laid out for four times the held share
    against the rows of every pair there could be, at the shape
    ``ling3.tokens8k`` runs it: outputs and the gradients of the tokens, the
    weights and both expert leaves, bf16. Then every pair on a held expert:
    the count that passes the rows, where the held experts are applied to
    every token by two dense products."""
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.models import moe

    tokens, top_k, held, published, d, f, tile = run.cfg['routed']
    keys = jax.random.split(jax.random.PRNGKey(36), 6)
    x, c = (jax.random.normal(key, (tokens, d), jnp.bfloat16)
            for key in keys[:2])
    w1 = (jax.random.normal(keys[2], (held, d, 2 * f), jnp.float32)
          / np.sqrt(d)).astype(jnp.bfloat16)
    w2 = (jax.random.normal(keys[3], (held, f, d), jnp.float32)
          / np.sqrt(f)).astype(jnp.bfloat16)
    experts, weights = moe.top_k_routing(jax.nn.sigmoid(jax.random.normal(
        keys[4], (tokens, published), jnp.float32)), top_k)
    all_held = jax.random.randint(keys[5], (tokens, top_k), 0, held)
    impl = 'pallas:interpret' if run.interpret else 'pallas'

    def layer(name, experts, over_share):
        def loss(x, weights, w1, w2):
            y, counts = moe.routed_experts(x, experts, weights, w1, w2,
                                           tuple(range(held)), published,
                                           tile_m=tile, impl=impl)
            return jnp.sum((y * c).astype(jnp.float32)), (y, counts)
        step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3),
                                          has_aux=True))
        # The capacity is read as the layer is traced: a share so large that
        # it is every pair lays out every pair's rows with no choice.
        compact, moe.CAPACITY_OVER_SHARE = moe.CAPACITY_OVER_SHARE, over_share
        try:
            if name == 'routed.compact':
                assert_mosaic(name, step.lower(x, weights, w1, w2))
            (_, (y, counts)), grads = run.compile(
                name + '.fwd+bwd', step, x, weights, w1, w2)(x, weights, w1, w2)
        finally:
            moe.CAPACITY_OVER_SHARE = compact
        return y, counts, grads

    capacity = moe.pairs_capacity(tokens * top_k, held, published, tile)
    failures = []
    for routing, picked in (('routed', experts), ('all held', all_held)):
        y, counts, grads = layer('routed.compact', picked,
                                 moe.CAPACITY_OVER_SHARE)
        want_y, want_counts, want_grads = layer('routed.full', picked,
                                                published)
        fwd_err = _rel_err(y, want_y)
        grad_err = max(_rel_err(g, t) for g, t in zip(grads, want_grads))
        fell_back = int(counts.sum()) > capacity
        run.say('routed experts [{} tokens, top {}, {} of {} held, {} -> {}, '
                '{}: {} held pairs against rows for {}{}]: fwd err {:.4%}, '
                'grad err {:.4%} (tol {:.2%}) against the rows of every '
                'pair'.format(tokens, top_k, held, published, d, f, routing,
                              int(counts.sum()), capacity,
                              ', the held experts on every token' if fell_back else '',
                              fwd_err, grad_err, GROUPED_TOL))
        if not (counts.tolist() == want_counts.tolist()
                and fwd_err <= GROUPED_TOL and grad_err <= GROUPED_TOL
                and fell_back == (routing == 'all held')):
            failures.append('routed ' + routing)
    return failures


def _stream_sub_layer_check(run, assert_mosaic):
    """A sub-layer between its hyper-connection maps through the kernels of
    ``ops.hyper_connections`` against the ``jax.numpy`` formulation at the
    shape ``xing4.tokens4k`` runs it, the maps as the configuration starts
    them: forward, and the gradients of the streams, of the sub-layer's own
    weights and of every leaf of the maps, bf16."""
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.models.latent_moe import (StreamMaps, StreamSubLayer,
                                                 mix_streams)

    tokens, n, d = run.cfg['streams']
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    x = jax.random.normal(keys[0], (1, tokens, n * d), jnp.bfloat16)
    w = (jax.random.normal(keys[1], (d, d), jnp.float32)
         / np.sqrt(d)).astype(jnp.bfloat16)
    c = jax.random.normal(keys[2], (1, tokens, n * d), jnp.bfloat16)
    start = dict(alpha_init=0.1, res_diagonal_init=4.0)
    sub = StreamSubLayer(streams=n, **start)
    params = sub.init(keys[3], x, lambda inner: inner)['params']

    def fn(w):
        return lambda inner: jnp.tanh(inner @ w)

    def kernels(params, x, w):
        return sub.apply({'params': params}, x, fn(w))[0]

    def plain(params, x, w):
        x4 = x.reshape(1, tokens, n, d)
        out, _ = mix_streams(x4, *StreamMaps(**start).apply(
            {'params': params}, x4), fn(w))
        return out.reshape(x.shape)

    def both(route):
        def loss(params, x, w):
            out = route(params, x, w)
            return jnp.sum((out * c).astype(jnp.float32)), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    kernel = both(kernels)
    assert_mosaic('streams', kernel.lower(params, x, w))
    (_, out), grads = run.compile('streams.fwd+bwd', kernel, params, x, w)(
        params, x, w)
    (_, want), want_grads = both(plain)(params, x, w)
    fwd_err = _rel_err(out, want)
    errs = jax.tree_util.tree_map(_rel_err, grads, want_grads)
    grad_err = max(jax.tree_util.tree_leaves(errs))
    run.say('stream sub-layer [{} tokens x {} streams of {}, bf16]: fwd err '
            '{:.4%}, grad err {:.4%} over {} leaves (tol {:.2%}) against '
            'jax.numpy'.format(tokens, n, d, fwd_err, grad_err,
                               len(jax.tree_util.tree_leaves(errs)),
                               STREAMS_TOL))
    ok = fwd_err <= STREAMS_TOL and grad_err <= STREAMS_TOL
    return [] if ok else ['streams']


def _kimi_delta_check(run, assert_mosaic):
    """The Pallas kernels of ``ops.kimi_delta`` against the ``jax.numpy``
    form of the same chunked rule at the shape ``ling3.tokens8k`` runs it,
    with every token's gate at its bound (the case the sub-blocks exist
    for: a decay of e^-5 a token and channel, e^-320 over a chunk): forward
    and all five gradients finite and equal, bf16."""
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.ops.kimi_delta import GATE_LOWER_BOUND, kda_rule

    t, h, d, chunk, sub = run.cfg['kda']
    keys = jax.random.split(jax.random.PRNGKey(13), 6)

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    q = (unit(jax.random.normal(keys[0], (1, t, h, d))) * d ** -0.5).astype(
        jnp.bfloat16)
    k = unit(jax.random.normal(keys[1], (1, t, h, d))).astype(jnp.bfloat16)
    v = jax.random.normal(keys[2], (1, t, h, d), jnp.bfloat16)
    g = GATE_LOWER_BOUND + 1e-3 * jax.random.uniform(keys[3], (1, t, h, d))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, t, h)))
    c = jax.random.normal(keys[5], (1, t, h, d), jnp.bfloat16)

    def rule(impl):
        def loss(q, k, v, g, beta):
            o = kda_rule(q, k, v, g, beta, chunk=chunk, sub_block=sub,
                         impl=impl)
            return jnp.sum((o * c).astype(jnp.float32)), o
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                          has_aux=True))

    kernel = rule('pallas:interpret' if run.interpret else 'pallas')
    assert_mosaic('kda', kernel.lower(q, k, v, g, beta))
    (_, o), grads = run.compile('kda.fwd+bwd', kernel, q, k, v, g, beta)(
        q, k, v, g, beta)
    (_, want), want_grads = rule('chunked')(q, k, v, g, beta)
    finite = all(bool(jnp.isfinite(a.astype(jnp.float32)).all())
                 for a in (o,) + tuple(grads))
    fwd_err = _rel_err(o, want)
    grad_err = max(_rel_err(a, b) for a, b in zip(grads, want_grads))
    run.say('kimi delta rule [{} tokens x {} heads of {}, chunks of {} in '
            'sub-blocks of {}, every gate within 1e-3 of {}, bf16]: finite '
            '{}, fwd err {:.4%}, grad err {:.4%} (tol {:.2%}) against '
            'jax.numpy'.format(t, h, d, chunk, sub, GATE_LOWER_BOUND, finite,
                               fwd_err, grad_err, KDA_TOL))
    ok = finite and fwd_err <= KDA_TOL and grad_err <= KDA_TOL
    return [] if ok else ['kda']


def phase_kernels(run):
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.ops import image_ops
    from petastorm_tpu.ops.flash_attention import flash_attention

    def assert_mosaic(name, lowered):
        # Only a compiled-mode lowering holds Mosaic calls; the interpreter
        # rehearsal has none to look for.
        if not run.interpret:
            assert 'tpu_custom_call' in lowered.as_text(), (
                '{} lowered without a Mosaic custom call'.format(name))

    def flash_loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       interpret=run.interpret)
                       .astype(jnp.float32) ** 2)

    def dense_loss(q, k, v):
        return jnp.sum(_dense_reference(q, k, v) ** 2)

    failures = []
    for name, dtype, b, t, h, d in run.cfg['flash']:
        keys = jax.random.split(jax.random.PRNGKey(t), 3)
        q, k, v = (jax.random.normal(key, (b, t, h, d), jnp.dtype(dtype))
                   for key in keys)
        fwd = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=run.interpret))
        bwd = jax.jit(jax.grad(flash_loss, argnums=(0, 1, 2)))
        assert_mosaic(name + '.fwd', fwd.lower(q, k, v))
        assert_mosaic(name + '.fwd+bwd', bwd.lower(q, k, v))
        out = run.compile('flash.{}.fwd'.format(name), fwd, q, k, v)(q, k, v)
        grads = run.compile('flash.{}.fwd+bwd'.format(name), bwd,
                            q, k, v)(q, k, v)
        want = jax.jit(_dense_reference)(q, k, v)
        want_grads = jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2)))(q, k, v)
        fwd_err = _rel_err(out, want)
        grad_err = max(_rel_err(g, w) for g, w in zip(grads, want_grads))
        finite = bool(jnp.isfinite(out.astype(jnp.float32)).all()) and all(
            bool(jnp.isfinite(g.astype(jnp.float32)).all()) for g in grads)
        run.say('flash {} [B={} T={} H={} D={} {} causal]: fwd err {:.4%} '
                '(tol {:.2%}), grad err {:.4%} (tol {:.2%})'.format(
                    name, b, t, h, d, dtype, fwd_err, FLASH_FWD_TOL,
                    grad_err, FLASH_GRAD_TOL))
        if not (finite and fwd_err <= FLASH_FWD_TOL
                and grad_err <= FLASH_GRAD_TOL):
            failures.append(name)

    failures += _grouped_product_check(run, assert_mosaic)
    failures += _routed_layouts_check(run, assert_mosaic)
    failures += _stream_sub_layer_check(run, assert_mosaic)
    failures += _kimi_delta_check(run, assert_mosaic)

    for shape in run.cfg['normalize']:
        images = jax.random.randint(jax.random.PRNGKey(shape[0]), shape, 0,
                                    256, jnp.int32).astype(jnp.uint8)
        if run.interpret:
            # normalize_images is the XLA reference off the TPU; the
            # rehearsal names the interpreter for the kernel itself.
            got = image_ops._normalize_pallas(
                images, *image_ops._scale_shift(), interpret=True)
        else:
            kernel = jax.jit(image_ops.normalize_images)
            assert_mosaic('normalize{}'.format(shape), kernel.lower(images))
            got = run.compile('normalize_images{}'.format(list(shape)),
                              kernel, images)(images)
        want = jax.jit(image_ops.normalize_images_reference)(images)
        got32 = np.asarray(got.astype(jnp.float32))
        want32 = np.asarray(want.astype(jnp.float32))
        differing = int((got32 != want32).sum())
        run.say('normalize_images {} -> {}: {} of {} elements differ from '
                'the XLA reference (max |diff| {:.3g})'.format(
                    list(shape), got.dtype, differing, got32.size,
                    float(np.abs(got32 - want32).max())))
        assert got.shape == shape and got.dtype == want.dtype
        if differing:
            failures.append('normalize{}'.format(list(shape)))
    assert not failures, 'kernel checks failed: {}'.format(failures)


# --------------------------------------------------------------------------
# entry
# --------------------------------------------------------------------------

def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    parser.add_argument(
        '--cpu-tiny', action='store_true',
        help='sandbox rehearsal: toy sizes on the CPU, Pallas kernels in '
             'interpret mode (needs JAX_PLATFORMS=cpu)')
    args = parser.parse_args(argv)

    import jax
    devices = jax.devices()
    device = {'platform': devices[0].platform,
              'kind': devices[0].device_kind, 'count': len(devices)}
    print('[platform={platform}] device_kind={kind!r} devices={count} '.format(
        **device) + 'jax={} jaxlib={} libtpu={} python={}'.format(
            jax.__version__, _package_version('jaxlib'),
            _package_version('libtpu'), sys.version.split()[0]), flush=True)
    wanted = 'cpu' if args.cpu_tiny else 'tpu'
    if device['platform'] != wanted:
        sys.exit('chip_smoke.py{} needs platform {!r}; jax reports {!r}. '
                 'Nothing was run.'.format(
                     ' --cpu-tiny' if args.cpu_tiny else '', wanted,
                     device['platform']))

    from petastorm_tpu.parallel import make_mesh
    from petastorm_tpu.utils import enable_compile_cache

    run = Run(TINY if args.cpu_tiny else FULL, interpret=args.cpu_tiny,
              platform=device['platform'])
    cache_dir = enable_compile_cache()
    entries_before = _cache_entries(cache_dir)
    run.say('compile cache: {} ({} entries before; placed by {})'.format(
        cache_dir, entries_before,
        'JAX_COMPILATION_CACHE_DIR' if os.environ.get(
            'JAX_COMPILATION_CACHE_DIR') else 'enable_compile_cache'))
    jax.monitoring.register_event_listener(
        lambda event, **_: run.cache_events.update([event]))

    # Every local chip, one 'data' axis: batch = per_chip x n, so the same
    # script serves one chip and the four of a host.
    run.n = len(devices)
    run.mesh = make_mesh({'data': run.n})
    run.workdir = tempfile.mkdtemp(prefix='chip_smoke_')
    t_start = time.perf_counter()
    try:
        for name, phase in (('environment', phase_environment),
                            ('write stores', phase_write_stores),
                            ('host reference read', phase_host_reference),
                            ('byte proof', phase_byte_proof),
                            ('resnet train', phase_resnet),
                            ('lm train', phase_lm),
                            ('hybrid lm train', phase_hybrid),
                            ('device cache', phase_device_cache),
                            ('kernels', phase_kernels)):
            with run.phase(name):
                phase(run)
    finally:
        shutil.rmtree(run.workdir, ignore_errors=True)

    run.say('-- wall time per phase')
    for name, seconds in run.phase_s.items():
        run.say('{:>22}: {:7.1f}s'.format(name, seconds))
    run.say('{:>22}: {:7.1f}s'.format('total',
                                      time.perf_counter() - t_start))
    run.say('-- compile seconds per program (total {:.1f}s)'.format(
        sum(run.compile_s.values())))
    for name, seconds in run.compile_s.items():
        run.say('{:>44}: {:6.2f}s'.format(name, seconds))
    run.say('compile cache: {} entries before, {} after; persistent-cache '
            'hits {} misses {}'.format(
                entries_before, _cache_entries(cache_dir),
                run.cache_events['/jax/compilation_cache/cache_hits'],
                run.cache_events['/jax/compilation_cache/cache_misses']))
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        run.say('device {} peak_bytes_in_use={} peak_bytes_reserved={} '
                'bytes_limit={}'.format(
                    d.id, *(stats.get(key, 'not reported') for key in (
                        'peak_bytes_in_use', 'peak_bytes_reserved',
                        'bytes_limit'))))

    result = json.dumps({'ok': True, 'device': device})
    if args.cpu_tiny:
        run.say('rehearsal complete (not a chip result): ' + result)
    else:
        print(result, flush=True)


if __name__ == '__main__':
    main()
