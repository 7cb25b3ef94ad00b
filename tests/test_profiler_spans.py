"""Lifecycle spans in the JAX profiler's trace, recorded on the CPU.

Every profiler capture of the test suite is in this one file, so that one
test worker at a time runs a profiler.
"""
import glob
import os
import subprocess
import sys

import jax
import pytest

from repro.core.events import EventLog

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _host_spans(directory: str) -> dict:
    """{name: (start_ns, end_ns, {stat: value})} of the capture's repro.* events."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        out[e.name] = (e.start_ns, e.end_ns, dict(e.stats))
    return out


@pytest.mark.filterwarnings("ignore:builtin type event_stats:DeprecationWarning")
def test_nested_lifecycle_spans_reach_the_profiler(tmp_path):
    log = EventLog()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with log.lifecycle("early") as early:  # opened before the capture
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with log.lifecycle("step", 3) as step:
                with log.lifecycle("batch") as batch:
                    jax.numpy.ones(4).block_until_ready()
        finally:
            jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    assert set(spans) == {"repro.step", "repro.batch"}
    assert spans["repro.step"][2] == {"span": step, "parent": early}
    assert spans["repro.batch"][2] == {"span": batch, "parent": step}
    s0, s1, _ = spans["repro.step"]
    b0, b1, _ = spans["repro.batch"]
    assert s0 <= b0 < b1 <= s1
    # the event log records the same spans as before
    assert [(e.kind, e.name) for e in log.events()] == [
        ("spawn", "early"), ("spawn", "step"), ("spawn", "batch"),
        ("exit", "batch"), ("exit", "step"), ("exit", "early")]


def test_events_module_loads_no_jax():
    script = ("import sys\n"
              "from repro.core.events import EventLog\n"
              "log = EventLog()\n"
              "with log.lifecycle('step', 0):\n"
              "    pass\n"
              "assert len(log.durations('step')) == 1\n"
              "print('jax' in sys.modules)\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "False"
