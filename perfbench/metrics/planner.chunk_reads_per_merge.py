"""Mean over answers that read a chunk of their chunk reads per
`_merge_raw` call (`QueryPlan.chunk_reads / QueryPlan.merges`): how many
chunk reads each fold into the answer's array takes in.  A view hit or
an empty answer reads nothing and is left out."""


def read(record):
    plans = [r.answer.plan for r in record.answered()]
    if any(getattr(p, "merges", None) is None for p in plans):
        return None
    ratios = [p.chunk_reads / p.merges for p in plans if p.merges]
    return sum(ratios) / len(ratios) if ratios else None
