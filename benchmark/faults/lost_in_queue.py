"""A pod lost in the queue: 1 pod in 997 that the loop pops is dropped
there, neither scheduled nor put back. It is never bound and nothing the
scheduler counts says so; only the client sees an old pod still pending."""


def after_scheduler(sched):
    real_pop, count = sched.queue.pop_batch, [0]

    def pop_batch(n):
        kept = []
        for qp in real_pop(n):
            count[0] += 1
            if count[0] % 997:
                kept.append(qp)
        return kept

    sched.queue.pop_batch = pop_batch
