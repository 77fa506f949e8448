"""Mean bytes per answer that its chunk reads sent host to device
(`QueryPlan.upload_bytes`)."""


def read(record):
    done = record.answered()
    counts = [getattr(r.answer.plan, "upload_bytes", None) for r in done]
    return sum(counts) / len(counts) if counts and None not in counts else None
