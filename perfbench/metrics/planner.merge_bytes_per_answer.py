"""Mean bytes per answer of the arrays `_merge_raw` allocated folding chunk
reads into the answer's array (`QueryPlan.merge_bytes`)."""


def read(record):
    done = record.answered()
    counts = [getattr(r.answer.plan, "merge_bytes", None) for r in done]
    return sum(counts) / len(counts) if counts and None not in counts else None
