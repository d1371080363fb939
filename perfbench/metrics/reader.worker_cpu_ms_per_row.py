"""CPU seconds of the reader pool's worker threads (/proc/self/task) over the
window's rows."""


def read(ctx):
    return 1e3 * (ctx['end']['worker_cpu_s'] - ctx['begin']['worker_cpu_s']) / ctx['rows']
