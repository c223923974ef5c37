"""Serving launcher: classification-view service over an LM-encoded corpus
(the paper's workload), a SQL front-end over the same engines, and a
pure-LM decode mode for the decode-shape configs.

  PYTHONPATH=src python -m repro.launch.serve --mode view --requests 2000
  PYTHONPATH=src python -m repro.launch.serve --mode sql            # REPL
  PYTHONPATH=src python -m repro.launch.serve --mode sql --script demo.sql
  PYTHONPATH=src python -m repro.launch.serve --mode sql \
      --execute "SHOW TABLES"
  PYTHONPATH=src python -m repro.launch.serve --mode sql \
      --serve 127.0.0.1:5433 --script schema.sql   # concurrent SQL server
  PYTHONPATH=src python -m repro.launch.serve --mode decode --arch tinyllama-1.1b

In server mode (`--serve HOST:PORT`), an optional --script/--execute runs
first against the shared executor (schema bootstrap), then the asyncio
server accepts N concurrent wire-protocol sessions (`repro.rdbms.client`
speaks it) until interrupted.

The view driver is an importable module (`repro.launch.view_driver`)
shared with `examples/serve_view.py` — no file-path loading hacks.
"""
from __future__ import annotations

import argparse

from repro.obs import clock


def serve_decode(arch: str, steps: int, batch: int, cache_len: int):
    import jax
    import jax.numpy as jnp
    from repro.configs import smoke_config
    from repro.models import build
    from repro.models.steps import init_cache, init_train_state, make_decode_step
    cfg = smoke_config(arch)
    mdl = build(cfg)
    state = init_train_state(mdl)
    cache = init_cache(mdl, batch, cache_len)
    dec = jax.jit(make_decode_step(mdl), donate_argnums=(1,))
    tok = jnp.zeros((batch, 1), jnp.int32)
    t0 = clock()
    for i in range(steps):
        tok, cache = dec(state["params"], cache, tok, jnp.asarray(i, jnp.int32))
    jax.block_until_ready(tok)
    dt = clock() - t0
    print(f"[serve] decode: {steps} steps x batch {batch} -> "
          f"{steps*batch/dt:.0f} tok/s ({dt/steps*1e3:.1f} ms/step)")


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
    ap.add_argument("--mode", default="view", choices=["view", "sql", "decode"])
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--requests", type=int, default=2000)
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--script", default=None,
                    help="sql mode: run this .sql file instead of the REPL")
    ap.add_argument("--execute", default=None,
                    help="sql mode: run these ;-separated statements")
    ap.add_argument("--serve", default=None, metavar="HOST:PORT",
                    help="sql mode: run the concurrent wire-protocol "
                         "server instead of the REPL (--script/--execute "
                         "bootstrap the schema first)")
    ap.add_argument("--slow-ms", type=float, default=None,
                    help="sql mode: log the span tree of any statement "
                         "slower than this many milliseconds")
    ap.add_argument("--log-statements", action="store_true",
                    help="sql mode: access log — one structured line per "
                         "served statement")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.mode == "decode":
        serve_decode(args.arch, args.steps, args.batch, args.cache_len)
    elif args.mode == "sql":
        serve_sql(args.script, args.execute, args.serve,
                  slow_ms=args.slow_ms, log_statements=args.log_statements)
    else:
        from repro.launch.view_driver import main as view_main
        view_main(["--requests", str(args.requests)])


if __name__ == "__main__":
    main()
