"""train_tokens_per_s: tokens trained (batch x sequence x steps) over the
wall time of the whole timed ``Trainer.run``, on the host clock."""


def read(run):
    if run.kind != "train":
        return None
    return run.tokens / run.window_s
