"""Mean partitions the planner read per answer in the window
(`PlannedAnswer.partitions_read`)."""


def read(record):
    done = record.answered()
    return sum(r.answer.partitions_read for r in done) / len(done) if done else None
