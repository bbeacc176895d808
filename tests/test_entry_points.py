"""The examples and the ``repro.launch.dedup`` command line, run in
process at small sizes: each finishes and prints its summary."""
import os
import runpy

import pytest

from repro.launch import dedup

EXAMPLES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples")


@pytest.fixture(autouse=True)
def _no_repo_compile_cache(monkeypatch, tmp_path):
    # With the variable set the entry points leave JAX's cache alone
    # instead of pointing it into the checkout.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


# (script, argv for its main or None for a plain script, summary line)
EXAMPLE_RUNS = {
    "quickstart": (None, "query(notes[0]): duplicate=True"),
    "incremental_ingest": ([], "final snapshot == one-shot host "
                               "clustering"),
    "bounded_ingest": (["--chunks", "4"], "cluster parity vs one-shot: OK"),
    "lsh_tuning": (None, "paper's operating point: r=2, b=50"),
    "query_service": (None, "all verdicts as expected"),
}


@pytest.mark.parametrize("name", list(EXAMPLE_RUNS))
def test_example_runs_to_its_summary(name, capsys):
    argv, summary = EXAMPLE_RUNS[name]
    path = os.path.join(EXAMPLES, f"{name}.py")
    if argv is None:
        runpy.run_path(path, run_name="__main__")
    else:
        runpy.run_path(path)["main"](argv)
    assert summary in capsys.readouterr().out


# (extra argv, the report line's mode and one more line it must print)
CLI_MODES = {
    "default": ([], "host[1 step(s)]: 90 docs ingested", None),
    "fused_ingest": (["--fused-ingest"], "host[1 step(s)]: 90 docs", None),
    "byte_estimate": (["--byte-ingest", "--estimate"],
                      "host[1 step(s)]: 90 docs", None),
    "estimate_jnp_band": (["--estimate", "--backend", "jnp",
                           "--batch", "band"],
                          "host[1 step(s)]: 90 docs", None),
    "streaming": (["--streaming", "--chunk", "32"],
                  "streaming[1 step(s)]: 90 docs", None),
    "streaming_bytes": (["--streaming", "--byte-ingest", "--chunk", "32"],
                        "streaming[1 step(s)]: 90 docs", None),
    "retain_small": (["--steps", "3", "--retain-budget", "small"],
                     "host[3 step(s)]: 90 docs", None),
    "estimate_query": (["--estimate", "--query", "8"],
                       "host[1 step(s)]: 90 docs",
                       "8/8 re-queried notes matched their clusters"),
}


@pytest.mark.parametrize("mode", list(CLI_MODES))
def test_dedup_cli_mode_reports(mode, capsys):
    extra, report, also = CLI_MODES[mode]
    dedup.main(["--notes", "60", "--dups", "30", *extra])
    out = capsys.readouterr().out
    assert out.startswith("corpus: 90 notes (30 injected near-dups)")
    assert report in out
    if also:
        assert also in out
        assert "novel note came back novel" in out
