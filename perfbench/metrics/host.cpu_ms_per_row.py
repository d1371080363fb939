"""CPU seconds (user and system, the process and its children) spent in the
window over its rows: what the host costs. Spreads by 3 to 4 % between runs
of one code (pool workers polling), so it bounds nothing end to end."""


def read(ctx):
    return 1e3 * (ctx['end']['cpu_s'] - ctx['begin']['cpu_s']) / ctx['rows']
