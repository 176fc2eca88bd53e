"""Train library: mean time the loop spends inside ``session.report``, from
the benchmark's own clock around the call, per step."""

UNIT = "ms"


def read(ctx, raw):
    if raw["kind"] != "train" or not raw.get("n_reports"):
        return None
    return 1e3 * raw["report_s"] / raw["n_reports"]
