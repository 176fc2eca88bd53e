"""Parallel, the experts' load by chip in a TRAINED step: the fullest chip's
routed pairs over the mean chip's (the experts in contiguous blocks, a chip
each), of the worst layer, averaged over the window's steps (1 is even): the
straggler an exchange of TOKENS waits for.  The step brings the experts to
the tokens and waits for none (a chip's rows are its own tokens' pairs), so
this reads what the routers do, and what that other exchange would cost.
The step's own counter (``routed_pairs``), read back with the loss every
step; None where the program counts none."""

UNIT = "ratio"


def read(ctx, raw):
    if raw.get("kind") != "train" or not raw.get("chip_load_max_over_mean"):
        return None
    return raw["chip_load_max_over_mean"]
