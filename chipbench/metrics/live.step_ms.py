"""Mean time of ``LiveLearner.step()``, merge and publish included (the
benchmark's ``bench.learner_step`` spans), over the window."""


def read(run):
    return run.spans.mean_ms("bench.learner_step")
