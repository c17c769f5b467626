"""CLI: ``python -m rafiki_tpu <command>``.

Reference parity: scripts/*.sh (unverified — SURVEY.md §2 deployment
row) started the reference's services as containers; here the whole
control plane is one process, so the CLI is the deployment surface:

  python -m rafiki_tpu serve [--host H] [--port P]   admin + web UI
  python -m rafiki_tpu version
"""

from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="rafiki_tpu")
    sub = parser.add_subparsers(dest="command")

    serve_p = sub.add_parser("serve", help="run the admin server (+ web UI)")
    serve_p.add_argument("--host", default=None)
    serve_p.add_argument("--port", type=int, default=None)

    sub.add_parser("version", help="print version")

    args = parser.parse_args(argv)
    # An explicit CPU request is applied before the first backend use
    # (the serve path imports the admin stack, which imports jax
    # transitively).
    from rafiki_tpu.utils.backend import honor_env_platform

    honor_env_platform()
    if args.command == "serve":
        from rafiki_tpu.admin.app import serve
        from rafiki_tpu.utils.backend import enable_compilation_cache

        enable_compilation_cache()
        serve(host=args.host, port=args.port)
        return 0
    if args.command == "version":
        import rafiki_tpu

        print(rafiki_tpu.__version__)
        return 0
    parser.print_help()
    return 1


if __name__ == "__main__":
    sys.exit(main())
