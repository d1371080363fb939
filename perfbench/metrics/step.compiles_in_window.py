"""Compilations (``jax.compile`` spans of the ``backend_compile`` stage, the
persistent cache's lookups included) that ended inside the window: none in
a sound run, where every shape was warmed in set-up."""

from perfbench import span_reduce


def read(ctx):
    window = span_reduce.window_of(ctx)
    if not window:
        return None
    return sum(1 for s in span_reduce.named(window['spans'], 'jax.compile')
               if s[span_reduce.ID] == 'backend_compile'
               and s[span_reduce.START] + s[span_reduce.DUR]
               < window['t1_ns'])
