"""plan_stats_s: seconds the planner spent computing the catalog's device
statistics (sketches, uniqueness, match ratio, multiplicity, selectivity):
the program's `plan.stats` span summed over the run. The window plans
nothing (its signature is cached), so the sum is set-up's."""
import spans


def read(record):
    return spans.span_total_s("plan.stats")
