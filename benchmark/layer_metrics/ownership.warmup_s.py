"""Device ownership: the first call of every shape the cell uses (weights
made, caches allocated, programs compiled or read from the compile cache).
Whether the run was cold or cached is on the earlier ``setup_detail`` line."""

UNIT = "s"


def read(ctx, raw):
    w = raw.get("warmup") or {}
    if raw["kind"] == "train":
        return w["init_state_s"] + w["first_step_s"] + w["second_step_s"]
    replica = raw.get("replica")
    if not replica:
        return None
    return (replica["t_ready"] - replica["t_chip"]) + sum(w["warm_posts_s"])
