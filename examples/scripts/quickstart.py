"""Quickstart: the full rafiki-tpu user journey, end to end.

Reference parity: examples/scripts/ (unverified — SURVEY.md §4
"quickstart scripts as integration tests"): create users → upload a
model → train job → inspect trials → inference job → predict.

Run against a live admin (scripts/start.sh):
    python examples/scripts/quickstart.py --host 127.0.0.1 --port 3000
Or fully self-contained (boots an admin in-process):
    python examples/scripts/quickstart.py --standalone
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(REPO))  # runnable straight from a checkout

TRAIN = "synthetic://images?classes=10&n=2048&seed=0"
VAL = "synthetic://images?classes=10&n=512&seed=1"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=3000)
    ap.add_argument("--standalone", action="store_true",
                    help="boot an in-process admin on an ephemeral port")
    ap.add_argument("--trials", type=int, default=4)
    args = ap.parse_args()

    server = None
    if args.standalone:
        import tempfile
        import threading

        from werkzeug.serving import make_server

        from rafiki_tpu.admin import Admin
        from rafiki_tpu.admin.app import AdminApp
        from rafiki_tpu.config import Config, set_config
        from rafiki_tpu.utils.backend import (enable_compilation_cache,
                                              honor_env_platform)

        # An explicit CPU request is applied before the first backend
        # use; this process trains and serves, so it owns the compile
        # cache too.
        honor_env_platform()
        enable_compilation_cache()

        cfg = Config(data_dir=Path(tempfile.mkdtemp(prefix="rafiki_quickstart_")))
        cfg.ensure_dirs()
        set_config(cfg)
        admin = Admin(config=cfg)
        server = make_server("127.0.0.1", 0, AdminApp(admin), threaded=True)
        threading.Thread(target=server.serve_forever, daemon=True).start()
        args.port = server.server_port
        print(f"standalone admin on port {args.port}")

    from rafiki_tpu.client import Client

    # 1. superadmin logs in and creates the two developer accounts
    sa = Client(args.host, args.port)
    sa.login("superadmin@rafiki", "rafiki")
    for email, role in [("modeldev@example.com", "MODEL_DEVELOPER"),
                        ("appdev@example.com", "APP_DEVELOPER")]:
        try:
            sa.create_user(email, "password", role)
        except Exception:
            pass  # already exists from a previous run

    # 2. the model developer uploads a template
    dev = Client(args.host, args.port)
    dev.login("modeldev@example.com", "password")
    template = REPO / "examples/models/image_classification/custom_cnn.py"
    try:
        dev.create_model("custom_cnn", "IMAGE_CLASSIFICATION", template,
                         "CustomCnn")
        print("uploaded model template custom_cnn")
    except Exception as e:
        print(f"model upload skipped: {e}")

    # 3. the app developer starts a train job
    app_name = f"quickstart_{int(time.time())}"
    appdev = Client(args.host, args.port)
    appdev.login("appdev@example.com", "password")
    appdev.create_train_job(app_name, "IMAGE_CLASSIFICATION", TRAIN, VAL,
                            {"MODEL_TRIAL_COUNT": args.trials},
                            model_names=["custom_cnn"], advisor_kind="gp")
    print(f"train job {app_name} started ({args.trials} trials)...")
    job = appdev.wait_until_train_job_has_stopped(app_name, timeout=3600,
                                                  poll_s=2.0)
    print(f"train job finished: {job['status']}")

    # 4. inspect trials
    for t in appdev.get_trials_of_train_job(app_name):
        score = "—" if t["score"] is None else f"{t['score']:.4f}"
        print(f"  trial {t['no']}: {t['status']:9s} score={score} "
              f"knobs={t['knobs']}")
    best = appdev.get_best_trials_of_train_job(app_name, max_count=2)
    print(f"best score: {best[0]['score']:.4f}")

    # 5. deploy + predict
    inf = appdev.create_inference_job(app_name)
    print(f"inference job RUNNING, predictor at {inf['predictor_host']}")
    from rafiki_tpu.model.dataset import dataset_utils

    ds = dataset_utils.load("synthetic://images?classes=10&n=16&seed=7")
    preds = appdev.predict(app_name, ds.x.tolist())
    import numpy as np

    acc = float(np.mean(np.argmax(np.asarray(preds), -1) == ds.y))
    print(f"ensemble accuracy on 16 fresh queries: {acc:.2f}")
    appdev.stop_inference_job(app_name)
    print("quickstart complete")
    if server is not None:
        server.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
