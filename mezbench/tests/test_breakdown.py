"""The breakdown tool on the CPU at small sizes: a traced serving run and
a traced onboarding run read the program's spans.

    PYTHONPATH=src python -m pytest -q mezbench/tests
"""

from __future__ import annotations

import json
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from mezbench.tools import breakdown  # noqa: E402

SEED = 4_000_000_017


def _small(cell):
    cell.config.update(frame_height=96, frame_width=128,
                       characterization_clip=16)


def _breakdown(capsys, workload, seconds):
    rc = breakdown.main(["--workload", workload, "--seed", str(SEED),
                         "--seconds", str(seconds)], require_tpu=False,
                        t_start=time.perf_counter(), patch=_small)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture(scope="module", autouse=True)
def _cpu_only():
    import jax

    if jax.default_backend() != "cpu":
        pytest.skip("these tests drive the CPU path")


def test_serving_breakdown_reads_the_poll_split(capsys):
    out = _breakdown(capsys, "testbed.steady", 2.0)
    spans = out["spans"]
    poll = spans["mez.poll"]
    assert poll[3] == [""]
    for name in ("mez.fetch", "mez.fleet_tick"):
        assert spans[name][3] == ["mez.poll"], name
    # session self time and the poll's children make up the poll
    children = sum(v[0] for v in spans.values() if "mez.poll" in v[3])
    assert poll[2] + children == pytest.approx(poll[0], rel=1e-9)
    layers = out["layers"]
    assert {"session_self_ms", "fleet_tick_host_us", "fetch_ms",
            "transform_ms", "deflate_ms"} <= set(layers)
    # the benchmark's span around each poll holds the program's
    assert poll[0] / poll[1] * 1e3 <= out["metrics"]["poll_ms.steady"][
        "value"]


def test_onboarding_breakdown_reads_the_host_share(capsys):
    out = _breakdown(capsys, "testbed.onboard", 0.1)
    assert out["spans"]["mez.char"][3] == [""]
    assert out["layers"]["char_host_ms"] > 0
