"""Serve engine: batch occupancy.  Mean ``stats()["active_slots"]`` over
``n_slots``, polled twice a second through the deployment handle."""

UNIT = "%"


def read(ctx, raw):
    if raw["kind"] != "serve" or not raw.get("polls"):
        return None
    active = [a for a, _ in raw["polls"]]
    return 100.0 * sum(active) / len(active) / raw["n_slots"]
