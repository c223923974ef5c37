"""Relational front-end over the HAZY engines (the paper's actual surface).

The paper's architecture puts classification *inside* the RDBMS: users
issue SQL DDL/DML/SELECTs against model-based views and the system picks
eager/lazy/hybrid maintenance under the covers. This package is that
surface for our engines:

  * `catalog`  — base entity tables + registered classification views
                 (single-view / multiclass / sharded, behind `EngineFacade`)
  * `lexer`/`parser`/`ast_nodes` — the SQL dialect
  * `wal`      — group-commit update log (WAL-style, replayable): heavy
                 write traffic amortizes into ONE engine round per commit
  * `planner`  — routes reads to the cheapest §3.5 tier and prices every
                 statement in touched tuples (the §3.4/§3.5 cost model)
  * `executor` — executes plans; `EXPLAIN` makes tier + cost user-visible;
                 `Session` scopes a prepared-statement cache per client
  * `concurrency` — the epoch gate: statement-scoped snapshot isolation
                 (readers pin the committed WAL batch index; commits
                 serialize exclusively behind them)
  * `wire`/`server`/`client` — length-prefixed-JSON protocol, the asyncio
                 SQL server (N concurrent sessions over ONE executor),
                 and the blocking client
                 (`python -m repro.launch.serve --serve ...`)
  * `repl`     — interactive / scripted entry point
                 (`python -m repro.launch.serve`)
"""
from repro.rdbms.ast_nodes import (AlterView, Commit, CreateTable,
                                   CreateView, Delete, ExecutePrepared,
                                   Explain, Insert, Param, Prepare, Select,
                                   Show, Update, UpdateModel, Where)
from repro.rdbms.catalog import Catalog, PlanError, SqlError, ViewDef
from repro.rdbms.client import ClientResult, ServerError, SqlClient
from repro.rdbms.concurrency import EpochGate
from repro.rdbms.executor import Executor, Result, Session
from repro.rdbms.lexer import LexError
from repro.rdbms.options import (DOWNSTREAM, TableOptions, ViewOptions,
                                 format_lag, parse_lag)
from repro.rdbms.parser import ParseError, parse
from repro.rdbms.planner import Plan, plan_statement
from repro.rdbms.server import ServerHandle, SqlServer, start_server_thread
from repro.rdbms.wal import UpdateLog, WalRecord
