"""Workers' parquet read seconds (worker_stage_timings) for a thousand rows;
nothing to read where the cache serves every chunk."""


def read(ctx):
    d = ctx['end']['timings'].get('read_s', 0.0) - ctx['begin']['timings'].get('read_s', 0.0)
    return 1e3 * d / ctx['rows'] if d > 0 else None
