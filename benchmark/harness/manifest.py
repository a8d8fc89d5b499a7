"""``BENCHMARK.json``: loading, the checks of names and units, and the
look-ups the harness makes by name.  The same limits as the driver's
contract, so that a bad entry fails here and not after a chip run."""

import importlib
import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
END_TO_END_SOURCES = ("host_clock", "device_trace")
METRIC_KEYS = {"end_to_end": {"name", "unit", "better", "bound", "source"},
               "per_layer": {"name", "unit", "better", "source", "layer",
                             "moves"}}


class ManifestError(ValueError):
    pass


def _need(ok, msg, *args):
    if not ok:
        raise ManifestError(msg % args)


def check_name(s, what):
    _need(isinstance(s, str) and NAME_RE.match(s) is not None,
          "%s %r is not a name (letters, digits, '_', '.', '-'; at most 64)",
          what, s)


def check_unit(s, what):
    _need(isinstance(s, str) and UNIT_RE.match(s) is not None,
          "unit %r of %s may hold 1 to 16 letters, digits and _ / %% . - only",
          s, what)


def validate(m):
    """Raises ``ManifestError`` on the first entry outside the contract's
    limits that the harness depends on."""
    for key in ("command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end", "per_layer"):
        _need(key in m, "BENCHMARK.json lacks %r", key)
    configs = {}
    for c in m["configs"]:
        check_name(c.get("name"), "configuration")
        _need(c["name"] not in configs, "configuration %r twice", c["name"])
        for k in c.get("reduced", ()):
            check_name(k, "reduced key of %s" % c["name"])
        configs[c["name"]] = c
    cells, pairs = set(), set()
    for w in m["workloads"]:
        check_name(w.get("name"), "cell")
        check_name(w.get("traffic"), "traffic of %s" % w["name"])
        _need(w["name"] not in cells, "cell %r twice", w["name"])
        _need(w.get("config") in configs, "cell %r names no configuration",
              w["name"])
        _need((w["config"], w["traffic"]) not in pairs,
              "configuration and traffic of %r appear twice", w["name"])
        _need(w.get("chips") in (1, 4), "cell %r: chips is 1 or 4", w["name"])
        cells.add(w["name"])
        pairs.add((w["config"], w["traffic"]))
    seen = set()
    e2e = {e.get("name") for e in m["end_to_end"]}
    for kind in ("end_to_end", "per_layer"):
        for e in m[kind]:
            check_name(e.get("name"), "metric")
            check_unit(e.get("unit"), e["name"])
            _need(e["name"] not in seen, "metric %r twice", e["name"])
            seen.add(e["name"])
            _need(e.get("better") in ("lower", "higher"),
                  "metric %r: better is lower or higher", e["name"])
            allowed = END_TO_END_SOURCES if kind == "end_to_end" else SOURCES
            _need(e.get("source") in allowed, "metric %r: source %r",
                  e["name"], e.get("source"))
            extra = set(e) - METRIC_KEYS[kind] - {"workloads"}
            _need(not extra, "metric %r has keys the contract refuses: %s",
                  e["name"], sorted(extra))
            for w in e.get("workloads", ()):
                _need(w in cells, "metric %r lists no cell %r", e["name"], w)
            if kind == "per_layer":
                _need(e.get("moves") in e2e, "metric %r moves no end-to-end "
                      "metric", e["name"])
    _need("setup_s" in e2e, "one end-to-end metric must be setup_s")
    return m


def load(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return validate(json.load(f))


def cell(m, name):
    for w in m["workloads"]:
        if w["name"] == name:
            return w
    raise ManifestError("no cell %r in BENCHMARK.json (cells: %s)"
                        % (name, ", ".join(w["name"] for w in m["workloads"])))


def config_entry(m, name):
    for c in m["configs"]:
        if c["name"] == name:
            return c
    raise ManifestError("no configuration %r" % (name,))


def metrics_of(m, kind, cell_name):
    """The metric entries of ``kind`` that the cell reports."""
    return [e for e in m[kind]
            if "workloads" not in e or cell_name in e["workloads"]]


def module(kind, name):
    """``benchmark/<kind>/<name>.py``: drivers, references, per-layer
    readers, FLOP functions and batch generators are all found this way, so
    a new one is a new file."""
    check_name(name, kind)
    return importlib.import_module("benchmark.%s.%s" % (kind, name))


def read_json(root, *parts):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)
