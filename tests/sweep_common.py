"""One train job through ``LocalScheduler`` (the benchmark's entry) at a tiny
size, and the span records it leaves: shared by ``test_span_bridge.py`` and
``test_setup_spans.py``."""

from rafiki_tpu import telemetry

MODEL_SRC = b"""
from rafiki_tpu.model.base import JaxModel
from rafiki_tpu.model.knobs import FixedKnob, FloatKnob
from rafiki_tpu.models.ff import _Mlp

class BridgeFF(JaxModel):
    @staticmethod
    def get_knob_config():
        return {
            "learning_rate": FloatKnob(1e-3, 3e-2, is_exp=True),
            "batch_size": FixedKnob(64),
            "epochs": FixedKnob(1),
            "seed": FixedKnob(0),
        }

    def build_module(self, num_classes, input_shape):
        return _Mlp(hidden_layers=1, hidden_units=32, num_classes=num_classes)
"""
TRAIN = "synthetic://images?classes=4&n=256&w=8&h=8&c=1&seed=0"
VAL = "synthetic://images?classes=4&n=128&w=8&h=8&c=1&seed=1"


def run_sweep(work, trials, pack, traced=False, fresh=False,
              train=TRAIN, val=VAL):
    """``trials`` trials, ``pack`` a round (1: the serial lane), under the
    profiler as the benchmark traces (``traced``: the python tracer off, the
    host tracer at its default); ``fresh`` empties the process's data-set,
    program and jit caches first, so that the first trial builds everything.
    Returns the span records and the trace's directory."""
    import jax

    from rafiki_tpu.config import Config, get_config, set_config
    from rafiki_tpu.model.dataset import dataset_utils
    from rafiki_tpu.ops.train import clear_program_cache
    from rafiki_tpu.scheduler import LocalScheduler
    from rafiki_tpu.store import MetaStore, ParamsStore

    prev = get_config()
    set_config(Config(data_dir=work / "data").ensure_dirs())
    store = MetaStore(work / "meta.sqlite3")
    params = ParamsStore(work / "params")
    model = store.create_model("BridgeFF", "IMAGE_CLASSIFICATION", None,
                               MODEL_SRC, "BridgeFF")
    job = store.create_train_job("bridge", "IMAGE_CLASSIFICATION", None,
                                 train, val, {"MODEL_TRIAL_COUNT": trials})
    store.create_sub_train_job(job["id"], model["id"])
    if fresh:
        dataset_utils.clear_cache()
        clear_program_cache()
        jax.clear_caches()
    telemetry.reset()
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(work / "trace"), profiler_options=opts)
    try:
        result = LocalScheduler(store, params).run_train_job(
            job["id"], n_workers=1, advisor_kind="gp", trial_pack=pack)
    finally:
        if traced:
            jax.profiler.stop_trace()
        set_config(prev)
    records = telemetry.span_records()
    store.close()
    assert result.status == "COMPLETED"
    assert [t["status"] for t in result.trials] == ["COMPLETED"] * trials
    return {"records": records, "trace_dir": str(work / "trace")}
