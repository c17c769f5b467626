"""Device programs (rafiki_tpu/ops/train.py): the serial epoch program
alone, ``train.epoch`` span seconds (dispatch to metrics on the host) over
the optimizer steps its ``steps`` tags count: milliseconds a step. Moves
trials_per_hour."""


def read(m):
    spans = [s for s in m["spans"] if s["name"] == "train.epoch"]
    steps = sum(int(s.get("tags", {}).get("steps", 0)) for s in spans)
    if steps == 0:
        return None
    return 1000.0 * sum(s["dur_s"] for s in spans) / steps
