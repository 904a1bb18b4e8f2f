"""Scheduler and prefix cache: prompt positions served from the cache
(SchedulerStats.tokens_prefill_skipped over the window) per prompt token of
the window's first admissions, in percent."""


def read(run):
    if not run.counters_start or not run.counters_end:
        return None
    admitted = sum(len(r.prompt) for r in run.requests
                   if run.t_window <= r.t_admit < run.t_window_end)
    if not admitted:
        return None
    skipped = (run.counters_end["tokens_prefill_skipped"]
               - run.counters_start["tokens_prefill_skipped"])
    return 100.0 * skipped / admitted
