"""argparse dispatcher for the framework CLI."""

from __future__ import annotations

import argparse
import importlib
import logging
import sys

# command name -> module under this package exposing add_parser(subparsers)
COMMANDS = {
    "serve": ".serve",
    "api": ".api",
    "chat": ".chat",
    "search": ".search",
    "ann": ".ann",
    "emb_test": ".emb_test",
    "load_csv": ".load_csv",
    "queue": ".queue_cmd",
    "worker": ".worker",
    "telegram_poll": ".telegram_poll",
    "tester": ".tester",
    "fetch_models": ".fetch_models",
    "synth_checkpoint": ".synth_checkpoint",
    "trace_export": ".trace_export",
}


def main(argv=None) -> int:
    logging.basicConfig(
        level=logging.INFO, format="%(asctime)s %(name)s %(levelname)s %(message)s"
    )
    parser = argparse.ArgumentParser(prog="django_assistant_bot_tpu")
    sub = parser.add_subparsers(dest="command", required=True)
    for name, module in COMMANDS.items():
        try:
            mod = importlib.import_module(module, package=__package__)
        except ImportError as e:
            # plane not built yet / optional dep missing: register an erroring stub
            p = sub.add_parser(name, help=f"(unavailable: {e})")
            p.set_defaults(func=lambda args, _e=e, _n=name: _unavailable(_n, _e))
            continue
        p = mod.add_parser(sub)
        p.set_defaults(func=mod.run)
    args = parser.parse_args(argv)
    try:
        return args.func(args) or 0
    except RuntimeError as e:
        # a second process on a one-chip host: say what happened and what
        # fits, instead of libtpu's traceback about a lockfile
        from ..utils.device import explain_backend_failure

        message = explain_backend_failure(args.command, e)
        if message is None:
            raise
        print(message, file=sys.stderr)
        return 3


def _unavailable(name: str, e: Exception) -> int:
    print(f"command {name!r} unavailable: {e}", file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
