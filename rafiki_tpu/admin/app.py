"""Admin REST app: HTTP surface over the Admin business logic.

Reference parity: rafiki/admin/app.py (unverified — SURVEY.md §2):
Flask routes mapping REST verbs onto `Admin`, with a JWT auth
decorator per route and multipart model upload. This environment has
no Flask, so the app is a small werkzeug WSGI application (werkzeug is
Flask's own HTTP core, so request/response semantics are identical).

Route table (mirrors the reference's client verbs):
  POST /tokens                       login → JWT
  POST /users                        create user            (admin)
  GET  /users                        list users             (admin)
  DELETE /users                      ban user               (admin)
  POST /models                       upload model template  (model dev)
  GET  /models                       list models
  GET  /models/<name>                model detail
  GET  /models/<name>/file           download template bytes
  POST /train_jobs                   create train job       (app dev)
  GET  /train_jobs                   list my train jobs
  GET  /train_jobs/<app>            latest job of app
  GET  /train_jobs/<app>/<v>        specific version
  POST /train_jobs/<app>/<v>/stop   stop job
  GET  /train_jobs/<app>/<v>/trials  trials (?type=best&max_count=k)
  GET  /trials/<id>                  trial detail
  GET  /trials/<id>/logs             trial logs
  GET  /trials/<id>/parameters       trained params blob
  POST /inference_jobs               deploy app             (app dev)
  GET  /inference_jobs/<app>/<v>     inference job detail
  POST /inference_jobs/<app>/<v>/stop
  POST /predict/<app>                run queries through the ensemble
  GET  /advisors/<id>/propose, POST /advisors/<id>/feedback
                                     (for process-per-chip workers)
  GET  /                             web admin UI (static SPA)
  GET  /healthz                      liveness
  GET  /metrics                      telemetry snapshot (read-only JSON;
                                     ?format=prom for Prometheus text)
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

from werkzeug.exceptions import HTTPException, NotFound
from werkzeug.routing import Map, Rule
from werkzeug.wrappers import Request, Response

from rafiki_tpu.admin.admin import Admin, NotFoundError
from rafiki_tpu.constants import UserType
from rafiki_tpu.utils.auth import AuthError, check_user_type, decode_token
from rafiki_tpu.utils.jsonable import jsonable as _jsonable

_WEB_DIR = Path(__file__).resolve().parent.parent / "web"


def _json(data: Any, status: int = 200) -> Response:
    return Response(json.dumps(data), status=status, mimetype="application/json")


class AdminApp:
    """WSGI app. ``werkzeug.serving.make_server(host, port, app)`` to run."""

    def __init__(self, admin: Admin):
        self.admin = admin
        self.url_map = Map([
            Rule("/", endpoint="web_index", methods=["GET"]),
            Rule("/healthz", endpoint="healthz", methods=["GET"]),
            Rule("/metrics", endpoint="metrics", methods=["GET"]),
            Rule("/tokens", endpoint="login", methods=["POST"]),
            Rule("/users", endpoint="create_user", methods=["POST"]),
            Rule("/users", endpoint="get_users", methods=["GET"]),
            Rule("/users", endpoint="ban_user", methods=["DELETE"]),
            Rule("/models", endpoint="create_model", methods=["POST"]),
            Rule("/models", endpoint="get_models", methods=["GET"]),
            Rule("/models/<name>", endpoint="get_model", methods=["GET"]),
            Rule("/models/<name>/file", endpoint="get_model_file", methods=["GET"]),
            Rule("/train_jobs", endpoint="create_train_job", methods=["POST"]),
            Rule("/train_jobs", endpoint="get_train_jobs", methods=["GET"]),
            Rule("/train_jobs/<app>", endpoint="get_train_job", methods=["GET"]),
            Rule("/train_jobs/<app>/<int:app_version>", endpoint="get_train_job",
                 methods=["GET"]),
            Rule("/train_jobs/<app>/stop", endpoint="stop_train_job",
                 methods=["POST"]),
            Rule("/train_jobs/<app>/<int:app_version>/stop",
                 endpoint="stop_train_job", methods=["POST"]),
            Rule("/train_jobs/<app>/trials", endpoint="get_trials",
                 methods=["GET"]),
            Rule("/train_jobs/<app>/<int:app_version>/trials",
                 endpoint="get_trials", methods=["GET"]),
            Rule("/trials/<trial_id>", endpoint="get_trial", methods=["GET"]),
            Rule("/trials/<trial_id>/logs", endpoint="get_trial_logs", methods=["GET"]),
            Rule("/trials/<trial_id>/parameters", endpoint="get_trial_parameters",
                 methods=["GET"]),
            Rule("/inference_jobs", endpoint="create_inference_job", methods=["POST"]),
            Rule("/inference_jobs/<app>", endpoint="get_inference_job",
                 methods=["GET"]),
            Rule("/inference_jobs/<app>/<int:app_version>",
                 endpoint="get_inference_job", methods=["GET"]),
            Rule("/inference_jobs/<app>/stop", endpoint="stop_inference_job",
                 methods=["POST"]),
            Rule("/inference_jobs/<app>/<int:app_version>/stop",
                 endpoint="stop_inference_job", methods=["POST"]),
            Rule("/predict/<app>", endpoint="predict", methods=["POST"]),
            Rule("/recovery", endpoint="recover", methods=["POST"]),
            Rule("/advisors/<advisor_id>/propose", endpoint="advisor_propose",
                 methods=["GET"]),
            Rule("/advisors/<advisor_id>/feedback", endpoint="advisor_feedback",
                 methods=["POST"]),
        ])

    # -- wsgi ----------------------------------------------------------------

    def __call__(self, environ, start_response):
        request = Request(environ)
        try:
            adapter = self.url_map.bind_to_environ(environ)
            endpoint, args = adapter.match()
            response = getattr(self, f"ep_{endpoint}")(request, **args)
        except NotFound:
            response = _json({"error": "Not found"}, 404)
        except HTTPException as e:
            response = _json({"error": e.description}, e.code or 500)
        except AuthError as e:
            response = _json({"error": str(e)}, 401)
        except NotFoundError as e:
            response = _json({"error": str(e)}, 404)
        except ValueError as e:
            response = _json({"error": str(e)}, 400)
        except Exception as e:  # don't leak stack traces to clients
            response = _json({"error": f"Internal error: {type(e).__name__}: {e}"}, 500)
        return response(environ, start_response)

    # -- auth helper ---------------------------------------------------------

    def _auth(self, request: Request,
              user_types: Optional[List[str]] = None) -> Dict[str, Any]:
        header = request.headers.get("Authorization", "")
        if not header.startswith("Bearer "):
            raise AuthError("Missing Bearer token")
        payload = decode_token(header[len("Bearer "):], self.admin.config.jwt_secret)
        if user_types is not None:
            check_user_type(payload.get("user_type", ""), user_types)
        return payload

    @staticmethod
    def _scope(user: Dict[str, Any]) -> Optional[str]:
        """Ownership scope for mutations: admins act on any user's jobs,
        developers only on their own."""
        if user.get("user_type") in (UserType.SUPERADMIN.value, UserType.ADMIN.value):
            return None
        return user.get("user_id")

    @staticmethod
    def _field(body: Dict[str, Any], key: str) -> Any:
        """Required request field; absence is the caller's fault (400)."""
        if key not in body:
            raise ValueError(f"Missing required field: {key}")
        return body[key]

    @staticmethod
    def _body(request: Request) -> Dict[str, Any]:
        if request.mimetype == "application/json":
            return request.get_json(force=True, silent=True) or {}
        # multipart/form-urlencoded: values arrive as strings; JSON-decode
        # the ones the API defines as structured.
        out: Dict[str, Any] = dict(request.form)
        for key in ("budget", "dependencies", "model_names", "queries", "knobs"):
            if key in out and isinstance(out[key], str):
                try:
                    out[key] = json.loads(out[key])
                except json.JSONDecodeError:
                    pass
        return out

    # -- endpoints -----------------------------------------------------------

    def ep_healthz(self, request: Request) -> Response:
        return _json({"status": "ok"})

    def ep_metrics(self, request: Request) -> Response:
        # Read-only process introspection, unauthenticated like
        # /healthz: the snapshot carries timings and counts, never
        # trial data or credentials. ?format=prom serves the same
        # snapshot in Prometheus text exposition for scrapers.
        from rafiki_tpu import telemetry

        if request.args.get("format") == "prom":
            from rafiki_tpu.obs import prom

            return Response(prom.to_prometheus(telemetry.snapshot()),
                            mimetype="text/plain; version=0.0.4")
        return _json(telemetry.snapshot())

    def ep_web_index(self, request: Request) -> Response:
        index = _WEB_DIR / "index.html"
        if index.exists():
            return Response(index.read_text(), mimetype="text/html")
        return _json({"service": "rafiki-tpu admin", "docs": "/healthz"})

    def ep_login(self, request: Request) -> Response:
        body = self._body(request)
        return _json(self.admin.authenticate_user(
            body.get("email", ""), body.get("password", "")))

    def ep_create_user(self, request: Request) -> Response:
        self._auth(request, [UserType.ADMIN.value])
        body = self._body(request)
        return _json(self.admin.create_user(
            self._field(body, "email"), self._field(body, "password"),
            self._field(body, "user_type")), 201)

    def ep_get_users(self, request: Request) -> Response:
        self._auth(request, [UserType.ADMIN.value])
        return _json(self.admin.get_users())

    def ep_ban_user(self, request: Request) -> Response:
        self._auth(request, [UserType.ADMIN.value])
        return _json(self.admin.ban_user(self._field(self._body(request), "email")))

    def ep_create_model(self, request: Request) -> Response:
        user = self._auth(request, [UserType.MODEL_DEVELOPER.value])
        body = self._body(request)
        if "model_file" in request.files:
            model_file = request.files["model_file"].read()
        else:
            model_file = body.get("model_file", "").encode()
        return _json(self.admin.create_model(
            user["user_id"], self._field(body, "name"), self._field(body, "task"),
            model_file, self._field(body, "model_class"),
            body.get("dependencies") or {},
            body.get("access_right", "PRIVATE"), body.get("docs", "")), 201)

    def ep_get_models(self, request: Request) -> Response:
        self._auth(request)
        return _json(self.admin.get_models(request.args.get("task")))

    def ep_get_model(self, request: Request, name: str) -> Response:
        self._auth(request)
        return _json(self.admin.get_model(name))

    def ep_get_model_file(self, request: Request, name: str) -> Response:
        user = self._auth(request, [UserType.MODEL_DEVELOPER.value])
        return Response(self.admin.get_model_file(name,
                                                  requester_id=user.get("user_id"),
                                                  requester_type=user.get("user_type")),
                        mimetype="application/octet-stream")

    def ep_create_train_job(self, request: Request) -> Response:
        user = self._auth(request, [UserType.APP_DEVELOPER.value])
        body = self._body(request)
        return _json(self.admin.create_train_job(
            user["user_id"], self._field(body, "app"), self._field(body, "task"),
            self._field(body, "train_dataset_uri"),
            self._field(body, "val_dataset_uri"), self._field(body, "budget"),
            model_names=body.get("model_names"),
            advisor_kind=body.get("advisor_kind", "gp"),
            devices_per_trial=int(body.get("devices_per_trial", 1))), 201)

    def ep_get_train_jobs(self, request: Request) -> Response:
        user = self._auth(request)
        return _json(self.admin.get_train_jobs(user["user_id"]))

    def ep_get_train_job(self, request: Request, app: str,
                         app_version: int = -1) -> Response:
        self._auth(request)
        return _json(self.admin.get_train_job(app, app_version))

    def ep_stop_train_job(self, request: Request, app: str,
                          app_version: int = -1) -> Response:
        user = self._auth(request, [UserType.APP_DEVELOPER.value])
        return _json(self.admin.stop_train_job(app, app_version,
                                               user_id=self._scope(user)))

    def ep_get_trials(self, request: Request, app: str,
                      app_version: int = -1) -> Response:
        self._auth(request)
        if request.args.get("type") == "best":
            max_count = int(request.args.get("max_count", 2))
            return _json(self.admin.get_best_trials_of_train_job(
                app, app_version, max_count))
        return _json(self.admin.get_trials_of_train_job(app, app_version))

    def ep_get_trial(self, request: Request, trial_id: str) -> Response:
        self._auth(request)
        return _json(self.admin.get_trial(trial_id))

    def ep_get_trial_logs(self, request: Request, trial_id: str) -> Response:
        self._auth(request)
        return _json(self.admin.get_trial_logs(trial_id))

    def ep_get_trial_parameters(self, request: Request, trial_id: str) -> Response:
        self._auth(request)
        return Response(self.admin.get_trial_parameters(trial_id),
                        mimetype="application/octet-stream")

    def ep_create_inference_job(self, request: Request) -> Response:
        user = self._auth(request, [UserType.APP_DEVELOPER.value])
        body = self._body(request)
        gateway = body.get("gateway")
        if gateway is not None and not isinstance(gateway, dict):
            raise ValueError("gateway must be an object of gateway-config "
                             "overrides (e.g. {\"policy\": \"least-loaded\"})")
        return _json(self.admin.create_inference_job(
            self._scope(user), self._field(body, "app"),
            int(body.get("app_version", -1)),
            max_models=int(body.get("max_models", 2)),
            gateway=gateway), 201)

    def ep_get_inference_job(self, request: Request, app: str,
                             app_version: int = -1) -> Response:
        self._auth(request)
        return _json(self.admin.get_inference_job(app, app_version))

    def ep_stop_inference_job(self, request: Request, app: str,
                              app_version: int = -1) -> Response:
        user = self._auth(request, [UserType.APP_DEVELOPER.value])
        return _json(self.admin.stop_inference_job(app, app_version,
                                                   user_id=self._scope(user)))

    def ep_predict(self, request: Request, app: str) -> Response:
        # No auth on predict: the reference's predictor frontend is an
        # unauthenticated app-facing endpoint.
        body = self._body(request)
        queries = body.get("queries", [])
        preds = self.admin.predict(app, queries,
                                   int(body.get("app_version", -1)))
        return _json({"predictions": _jsonable(preds)})

    def ep_recover(self, request: Request) -> Response:
        self._auth(request, [UserType.ADMIN.value])
        body = self._body(request)
        stale = body.get("stale_after_s")
        # Default async: re-training orphans can outlive any HTTP timeout.
        wait = bool(body.get("wait", False))
        return _json(self.admin.recover_trials(
            float(stale) if stale is not None else None, wait=wait))

    def ep_advisor_propose(self, request: Request, advisor_id: str) -> Response:
        self._auth(request)
        try:
            knobs = self.admin.services.advisors.propose(advisor_id)
        except KeyError:
            raise NotFoundError(f"No advisor {advisor_id!r}")
        return _json({"knobs": knobs})

    def ep_advisor_feedback(self, request: Request, advisor_id: str) -> Response:
        self._auth(request)
        body = self._body(request)
        try:
            self.admin.services.advisors.feedback(
                advisor_id, float(self._field(body, "score")),
                self._field(body, "knobs"))
        except KeyError:
            raise NotFoundError(f"No advisor {advisor_id!r}")
        return _json({"ok": True})


def make_admin_app(admin: Optional[Admin] = None) -> AdminApp:
    return AdminApp(admin or Admin())


def serve(host: Optional[str] = None, port: Optional[int] = None,
          admin: Optional[Admin] = None):
    """Blocking server entry point (scripts/start_admin.py uses this)."""
    from werkzeug.serving import make_server

    from rafiki_tpu.utils.backend import honor_env_platform

    honor_env_platform()  # a CPU request lands before the first backend use
    admin = admin or Admin()
    app = AdminApp(admin)
    host = host or admin.config.admin_host
    port = port or admin.config.admin_port
    server = make_server(host, port, app, threaded=True)
    print(f"rafiki-tpu admin listening on http://{host}:{port}")
    try:
        server.serve_forever()
    finally:
        admin.stop()
