"""Host seconds per answer in the planner's selection, the self time of the
program's ``planner.select`` span: view check, selectivity, features,
candidates, the sketch prior, the outlier bitmaps and `find_outliers`, the
funnel's strata.  The outlier reads inside it are left out."""
from perfbench.harness import spans


def read(record):
    if record.events is None:
        return None
    secs = spans.self_seconds(record.events).get("planner.select")
    done = record.answered()
    return secs / len(done) if secs and done else None
