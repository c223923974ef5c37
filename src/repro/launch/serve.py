"""SQL serving launcher: a REPL, a script runner, or the concurrent
wire-protocol server, over the classification views' engines.

  PYTHONPATH=src python -m repro.launch.serve                      # REPL
  PYTHONPATH=src python -m repro.launch.serve --script demo.sql
  PYTHONPATH=src python -m repro.launch.serve --execute "SHOW TABLES"
  PYTHONPATH=src python -m repro.launch.serve \
      --serve 127.0.0.1:5433 --script schema.sql   # concurrent SQL server

In server mode (`--serve HOST:PORT`), an optional --script/--execute runs
first against the shared executor (schema bootstrap), then the asyncio
server accepts N concurrent wire-protocol sessions (`repro.rdbms.client`
speaks it) until interrupted.
"""
from __future__ import annotations

import argparse


def serve_sql(script: str = None, execute: str = None, serve: str = None,
              slow_ms: float = None, log_statements: bool = False):
    from repro.rdbms.executor import Executor
    from repro.rdbms.repl import repl, run_script
    ex = Executor(slow_ms=slow_ms)
    if slow_ms is not None or log_statements:
        import logging
        logging.basicConfig(level=logging.INFO)  # slow/access logs visible
    if serve:
        import asyncio
        from repro.rdbms.server import SqlServer
        host, _, port = serve.rpartition(":")
        if not host or not port.isdigit():
            raise SystemExit(f"--serve wants HOST:PORT, got {serve!r}")
        # schema bootstrap runs before the first connection is accepted
        if script:
            with open(script) as fh:
                run_script(fh.read(), ex)
        elif execute:
            run_script(execute, ex)
        # the freshness scheduler runs for the server's whole lifetime:
        # views with a target_lag are refreshed in the background while
        # sessions are served (idle ticks are one catalog scan)
        from repro.scheduler import FreshnessScheduler
        refresher = FreshnessScheduler(ex).start()

        async def _serve():
            server = SqlServer(ex, host=host, port=int(port),
                               log_statements=log_statements)
            await server.start()
            print(f"[serve] sql server on {server.host}:{server.port} "
                  f"(length-prefixed JSON; freshness scheduler on; "
                  f"Ctrl-C to stop)")
            try:
                await server.serve_forever()
            finally:
                refresher.stop()

        try:
            asyncio.run(_serve())
        except KeyboardInterrupt:
            print("[serve] sql server stopped")
        return
    if script:
        with open(script) as fh:
            run_script(fh.read(), ex)
    elif execute:
        run_script(execute, ex)
    else:
        repl(ex)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--script", default=None,
                    help="run this .sql file instead of the REPL")
    ap.add_argument("--execute", default=None,
                    help="run these ;-separated statements")
    ap.add_argument("--serve", default=None, metavar="HOST:PORT",
                    help="run the concurrent wire-protocol server instead "
                         "of the REPL (--script/--execute bootstrap the "
                         "schema first)")
    ap.add_argument("--slow-ms", type=float, default=None,
                    help="log the span tree of any statement slower than "
                         "this many milliseconds")
    ap.add_argument("--log-statements", action="store_true",
                    help="access log — one structured line per served "
                         "statement")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    serve_sql(args.script, args.execute, args.serve,
              slow_ms=args.slow_ms, log_statements=args.log_statements)


if __name__ == "__main__":
    main()
