"""Traffic: a traffic file's parameters -> requests, and the loop that
sends them.

A traffic file (``bench/traffic/<name>.json``) holds only parameters and
names its kind (``"kind"``); the kind is a module
``bench/traffic/<kind>.py`` with

- ``make_requests(params, dfs, seed) -> list[Req]``: the requests, over
  a vocabulary whose document frequencies are ``dfs`` (token -> nodes
  carrying it), the same for the same seed;
- ``run_window(svc, requests, params, seconds, on_start)``: sends them
  to the service for ``seconds`` and returns ``(records, t0, t1)``,
  every request sent with its times and answer, and the window's start
  and end on ``time.perf_counter``.

A new mix of an existing kind is a new data file; a new kind is a new
module, found by name.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import graphgen

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass(frozen=True)
class Req:
    """One request: keyword tokens and the answers wanted."""

    keywords: tuple
    k: int


def load_params(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def kind(params: dict):
    """The module of a traffic file's kind."""
    return graphgen.load_module(TRAFFIC_DIR, params["kind"], "traffic")
