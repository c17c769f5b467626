"""Device programs (rafiki_tpu/ops/train.py): the packed epoch program
alone, ``train.packed_epoch`` span seconds (dispatch to metrics on the
host) over the optimizer steps its ``steps`` tags count, where
train_step_ms.sweep also holds the pack's initialisation. Moves
trials_per_hour."""


def read(m):
    spans = [s for s in m["spans"] if s["name"] == "train.packed_epoch"]
    steps = sum(int(s.get("tags", {}).get("steps", 0)) for s in spans)
    if steps == 0:
        return None
    return 1000.0 * sum(s["dur_s"] for s in spans) / steps
