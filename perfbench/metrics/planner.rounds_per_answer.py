"""Mean escalation rounds per answer in the window (`QueryPlan.rounds`)."""


def read(record):
    done = record.answered()
    return sum(r.answer.plan.rounds for r in done) / len(done) if done else None
