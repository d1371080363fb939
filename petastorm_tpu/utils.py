"""Small shared utilities.

Parity: reference ``petastorm/utils.py:30-47`` (``run_in_subprocess``). The
reference's other utils live elsewhere here: ``decode_row`` ->
``unischema.decode_rows``, ``add_to_dataset_metadata`` ->
``storage.ParquetStore.write_common_metadata``.
"""


def drain_queue(bounded_queue, buffer, max_items):
    """Move up to ``max_items`` ready items from a ``queue.Queue`` into a
    consumer-local ``buffer`` (deque) under ONE mutex acquisition — the
    batched-pop primitive behind the worker pool's result handoff
    (``ThreadPool._pop_result``; a per-item ``Queue.get`` costs a lock
    round trip each). The cap matters: every drained slot is capacity the
    producers refill, so callers size it to bound how far undelivered
    items may overshoot the queue's nominal depth. Producers blocked on
    the bounded put are woken for the freed capacity. Returns the number
    of items moved.

    NOT used by the JaxLoader consumer: its drain must keep staged device
    batches within the ``prefetch`` bound, so it shrinks the queue's live
    ``maxsize`` by the drained count and skips the wakeup — see
    ``JaxLoader.__next__``."""
    with bounded_queue.mutex:
        take = min(len(bounded_queue.queue), max_items)
        for _ in range(take):
            buffer.append(bounded_queue.queue.popleft())
        if take > 0:
            bounded_queue.not_full.notify_all()
    return take


def cached_namedtuple(cache, type_name, names):
    """Namedtuple type for ``names``, memoized in the caller's ``cache`` dict.

    Consumers that assemble batches from dict payloads (``JaxLoader``,
    ``RemoteReader``) must hand out the SAME type per field set — type
    equality is what lets downstream code (e.g. ``tf.data`` structure
    checks) treat consecutive batches as one structure.
    """
    nt = cache.get(names)
    if nt is None:
        from collections import namedtuple
        nt = namedtuple(type_name, names)
        cache[names] = nt
    return nt


def enable_compile_cache():
    """Turn on jax's persistent compilation cache for this process and
    return its directory. Entry points (``chip_smoke.py``, ``perfbench``'s
    harness, the examples, ``__graft_entry__``) call this before their
    first jit; library modules never set global jax config.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it itself and
    nothing is set here, so the cache can be placed from outside. Otherwise
    it lives at one fixed path inside the checkout (git-ignored): the path
    is part of the cache key, so a temp name, a pid or a timestamp would
    never hit.

    Either way a program's metadata is made part of its key
    (``jax_compilation_cache_include_metadata_in_key``; jax leaves it out by
    default): the scopes an instruction was traced in are metadata, and
    ``Tracer.op_scopes()`` reads them from the compiled step's text, so a
    step that differs from a cached one only in a ``jax.named_scope`` has
    to be a program of its own, or it would come back from the cache under
    the other one's names.
    """
    import os
    import jax
    jax.config.update('jax_compilation_cache_include_metadata_in_key', True)
    placed = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if placed:
        return placed
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        '.jax_compile_cache')
    jax.config.update('jax_compilation_cache_dir', path)
    return path


def run_in_subprocess(func, *args, **kwargs):
    """Run ``func(*args, **kwargs)`` in a one-shot subprocess and return its
    result — isolates memory leaks / library state from the calling process
    (the reference uses it so pyarrow allocations don't accumulate in tests
    and benchmarks). The child is spawned, never forked: a parent that
    holds libtpu (or any live thread) must not fork — see
    ``workers/exec_in_new_process.py`` — so ``func`` must be importable.
    """
    import multiprocessing

    with multiprocessing.get_context('spawn').Pool(1) as pool:
        return pool.apply(func, args, kwargs)
