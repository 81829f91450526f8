"""Platform glue: log daemon, remote config, deployment agents, CLI surface.

Mirrors the reference's MLOps/deployment plane behavior
(core/mlops/mlops_runtime_log_daemon.py, mlops_configs.py,
cli/edge_deployment/client_runner.py) on the TPU-pod-shaped local
implementations.
"""

import json
import os
import zipfile

import pytest

from fedml_tpu.agent import (
    STATUS_FAILED,
    STATUS_FINISHED,
    STATUS_RUNNING,
    Agent,
    agent_state,
    login,
    logout,
    submit_job,
)
from fedml_tpu.cli import main as cli_main
from fedml_tpu.core.mlops.log_daemon import LogProcessor, MLOpsRuntimeLogDaemon
from fedml_tpu.core.mlops.remote_config import RemoteConfig


# ---------------------------------------------------------------------------
# log daemon
# ---------------------------------------------------------------------------


def _write_lines(path, lines):
    with open(path, "a") as f:
        f.writelines(line + "\n" for line in lines)


def test_log_processor_ships_and_resumes(tmp_path):
    log = tmp_path / "run.log"
    dest = tmp_path / "shipped"
    _write_lines(log, [f"line-{i}" for i in range(5)])

    proc = LogProcessor(str(log), "r1", 0, f"dir:{dest}")
    assert proc.poll_once() == 5
    # nothing new → nothing shipped; index persisted
    assert proc.poll_once() == 0

    _write_lines(log, ["line-5", "line-6"])
    assert proc.poll_once() == 2

    out = (dest / "run_r1_edge_0.log").read_text().splitlines()
    assert out == [f"line-{i}" for i in range(7)]

    # a NEW processor (process restart) resumes from the saved line index
    proc2 = LogProcessor(str(log), "r1", 0, f"dir:{dest}")
    assert proc2.poll_once() == 0


def test_log_processor_holds_back_partial_line(tmp_path):
    log = tmp_path / "run.log"
    dest = tmp_path / "shipped"
    with open(log, "w") as f:
        f.write("complete\npart")  # writer caught mid-line
    proc = LogProcessor(str(log), "r3", 0, f"dir:{dest}")
    assert proc.poll_once() == 1  # only the terminated line ships
    with open(log, "a") as f:
        f.write("ial\n")
    assert proc.poll_once() == 1
    out = (dest / "run_r3_edge_0.log").read_text().splitlines()
    assert out == ["complete", "partial"]  # never truncated


def test_log_processor_failing_sink_keeps_index(tmp_path):
    log = tmp_path / "run.log"
    _write_lines(log, ["a", "b", "c"])
    calls = []

    def flaky_sink(run_id, edge_id, lines):
        calls.append(list(lines))
        return len(calls) > 1  # first ship fails

    proc = LogProcessor(str(log), "r2", 1, flaky_sink)
    assert proc.poll_once() == 0  # sink down: index unchanged
    assert proc.poll_once() == 3  # retry ships the same batch
    assert calls[0] == calls[1]


def test_log_processor_resets_on_truncation(tmp_path):
    log = tmp_path / "run.log"
    dest = tmp_path / "shipped"
    _write_lines(log, ["old-1", "old-2", "old-3"])
    proc = LogProcessor(str(log), "r4", 0, f"dir:{dest}")
    assert proc.poll_once() == 3
    log.write_text("new-1\n")  # rotation: file restarts smaller
    assert proc.poll_once() == 1  # offset reset, new content ships
    out = (dest / "run_r4_edge_0.log").read_text().splitlines()
    assert out[-1] == "new-1"


def test_log_daemon_registry(tmp_path):
    MLOpsRuntimeLogDaemon.reset_instance()
    log = tmp_path / "run.log"
    _write_lines(log, ["x"])
    daemon = MLOpsRuntimeLogDaemon.get_instance(f"dir:{tmp_path / 'out'}")
    daemon.start_log_processor("r", 0, str(log), upload_interval_s=0.05)
    try:
        deadline = 50
        import time

        for _ in range(deadline):
            out = tmp_path / "out" / "run_r_edge_0.log"
            if out.exists() and out.read_text().strip() == "x":
                break
            time.sleep(0.1)
        else:
            raise AssertionError("daemon thread never shipped the line")
    finally:
        MLOpsRuntimeLogDaemon.reset_instance()


# ---------------------------------------------------------------------------
# remote config
# ---------------------------------------------------------------------------


def test_remote_config_file_fetch_and_cache_fallback(tmp_path):
    RemoteConfig.reset_instance()
    src = tmp_path / "cfg.json"
    src.write_text(json.dumps({
        "mqtt_config": {"BROKER_HOST": "h", "BROKER_PORT": 1883},
        "s3_config": {"BUCKET_NAME": "b"},
    }))
    rc = RemoteConfig(str(src), cache_dir=str(tmp_path / "cache"))
    cfg = rc.fetch_configs(["mqtt_config", "s3_config"])
    assert cfg["mqtt_config"]["BROKER_HOST"] == "h"

    # source disappears → served from cache with a warning, not an error
    src.unlink()
    cfg2 = rc.fetch_configs(["mqtt_config"])
    assert cfg2["mqtt_config"]["BROKER_PORT"] == 1883


def test_remote_config_no_source_no_cache_raises(tmp_path):
    import pytest

    rc = RemoteConfig(str(tmp_path / "missing.json"),
                      cache_dir=str(tmp_path / "cache"))
    with pytest.raises(RuntimeError):
        rc.fetch_configs()


def test_remote_config_unwraps_data_envelope(tmp_path):
    # the reference endpoint nests payload under {"data": ...}
    src = tmp_path / "cfg.json"
    src.write_text(json.dumps({"data": {"ml_ops_config": {"LOG_SERVER": "u"}}}))
    rc = RemoteConfig(str(src), cache_dir=str(tmp_path / "cache"))
    assert rc.fetch_configs(["ml_ops_config"])["ml_ops_config"][
        "LOG_SERVER"] == "u"


# ---------------------------------------------------------------------------
# agents
# ---------------------------------------------------------------------------


def _make_package(tmp_path, name, entry_body, entry="main.py"):
    pkg_dir = tmp_path / name
    pkg_dir.mkdir()
    (pkg_dir / entry).write_text(entry_body)
    pkg = tmp_path / f"{name}.zip"
    with zipfile.ZipFile(pkg, "w") as z:
        z.write(pkg_dir / entry, entry)
        z.writestr("fedml_package.json",
                   json.dumps({"type": "client", "entry_point": entry}))
    return str(pkg)


def test_agent_runs_job_to_finished(tmp_path):
    pkg = _make_package(
        tmp_path, "ok",
        "import sys, json\n"
        "json.dump({'args': sys.argv[1:]}, open('out.json', 'w'))\n",
    )
    jobs = str(tmp_path / "jobs")
    job_id = submit_job(pkg, jobs, run_args=["--lr", "0.1"])
    agent = Agent(jobs, str(tmp_path / "work"))
    result = agent.run_once()
    assert result is not None and result.job_id == job_id
    assert result.status == STATUS_FINISHED
    out = json.load(open(os.path.join(result.run_dir, "out.json")))
    assert out["args"] == ["--lr", "0.1"]
    # full observable FSM, reference status names
    statuses = agent.job_statuses(job_id)
    assert statuses[0] == "UPGRADING" and STATUS_RUNNING in statuses
    assert statuses[-1] == STATUS_FINISHED
    # queue drained
    assert agent.run_once() is None


def test_agent_reports_failed_on_nonzero_exit(tmp_path):
    pkg = _make_package(tmp_path, "bad", "raise SystemExit(3)\n")
    jobs = str(tmp_path / "jobs")
    submit_job(pkg, jobs)
    result = Agent(jobs, str(tmp_path / "work")).run_once()
    assert result.status == STATUS_FAILED and result.returncode == 3


def test_agent_rejects_zip_slip(tmp_path):
    evil = tmp_path / "evil.zip"
    with zipfile.ZipFile(evil, "w") as z:
        z.writestr("../../escape.py", "print('pwn')\n")
        z.writestr("fedml_package.json",
                   json.dumps({"entry_point": "main.py"}))
    jobs = str(tmp_path / "jobs")
    submit_job(str(evil), jobs)
    result = Agent(jobs, str(tmp_path / "work")).run_once()
    assert result.status == STATUS_FAILED
    # '../../escape.py' relative to work/<job>/ would land in tmp_path itself
    assert not (tmp_path / "escape.py").exists()


def test_agent_requeues_stale_claim(tmp_path):
    pkg = _make_package(tmp_path, "ok2", "print('ran')\n")
    jobs = str(tmp_path / "jobs")
    job_id = submit_job(pkg, jobs)
    # a dead agent's claim: rename pending → claimed and backdate it
    src = os.path.join(jobs, f"{job_id}.job.json")
    claimed = os.path.join(jobs, f"{job_id}.job.claimed")
    os.rename(src, claimed)
    old = 10_000.0
    os.utime(claimed, (os.path.getmtime(claimed) - old,) * 2)

    agent = Agent(jobs, str(tmp_path / "work"), stale_claim_s=3600.0)
    result = agent.run_once()  # revives the orphan and runs it
    assert result is not None and result.status == STATUS_FINISHED
    assert not os.path.exists(claimed)  # finished claims are reaped


def test_claim_refreshes_mtime_so_queued_age_does_not_count(tmp_path):
    # a job that sat in the queue longer than stale_claim_s must NOT look
    # stale the instant it is claimed (ADVICE r2: rename preserves submit
    # mtime, letting a peer steal and double-run the job)
    pkg = _make_package(tmp_path, "aged", "print('ran')\n")
    jobs = str(tmp_path / "jobs")
    job_id = submit_job(pkg, jobs)
    pending = os.path.join(jobs, f"{job_id}.job.json")
    os.utime(pending, (os.path.getmtime(pending) - 10_000.0,) * 2)

    agent = Agent(jobs, str(tmp_path / "work"), stale_claim_s=3600.0)
    desc = agent._claim_next()
    assert desc["job_id"] == job_id
    # the claim filename is agent-unique so utime/open success proves
    # ownership even if a reviver re-pends and a peer re-claims the job
    claimed = os.path.join(jobs, f"{job_id}.job.claimed.{agent.agent_id}")
    import time as _time
    assert _time.time() - os.path.getmtime(claimed) < 60.0
    # a peer's reviver pass leaves the fresh claim alone
    peer = Agent(jobs, str(tmp_path / "work2"), stale_claim_s=3600.0)
    peer._requeue_stale_claims()
    assert os.path.exists(claimed)
    assert not os.path.exists(pending)


def test_stop_file_cleared_so_resubmitted_job_id_runs(tmp_path):
    from fedml_tpu.agent import request_stop

    pkg = _make_package(tmp_path, "stopme",
                        "import time\n"
                        "open('started', 'w').close()\n"
                        "time.sleep(60)\n")
    jobs = str(tmp_path / "jobs")
    agent = Agent(jobs, str(tmp_path / "work"))
    job_id = submit_job(pkg, jobs, job_id="job-fixed")
    request_stop(job_id, jobs)  # stop lands before the job even starts
    result = agent.run_once()
    assert result.status in (STATUS_FINISHED, STATUS_FAILED)
    # the kill switch must not survive to murder a resubmission of the id
    assert not os.path.exists(os.path.join(jobs, f"{job_id}.stop"))
    ok_pkg = _make_package(tmp_path, "ok3", "print('second life')\n")
    submit_job(ok_pkg, jobs, job_id="job-fixed")
    result2 = agent.run_once()
    assert result2.status == STATUS_FINISHED


def test_remote_config_explicit_params_do_not_hijack_singleton(tmp_path):
    RemoteConfig.reset_instance()
    default = RemoteConfig.get_instance()
    src = tmp_path / "cfg.json"
    src.write_text(json.dumps({"mqtt_config": {"host": "x"}}))
    explicit = RemoteConfig.get_instance(str(src),
                                         cache_dir=str(tmp_path / "c"))
    # explicit params → standalone instance honoring BOTH params...
    assert explicit.uri == str(src)
    assert explicit.cache_dir == str(tmp_path / "c")
    # ...and the process-wide default is untouched
    assert RemoteConfig.get_instance() is default
    RemoteConfig.reset_instance()


def test_login_logout_roundtrip(tmp_path):
    sd = str(tmp_path / "state")
    state = login("acct-7", role="server", state_dir=sd)
    assert state["role"] == "server"
    assert agent_state(state_dir=sd)["account_id"] == "acct-7"
    assert logout(state_dir=sd)
    assert agent_state(state_dir=sd) is None
    assert not logout(state_dir=sd)


# ---------------------------------------------------------------------------
# CLI deployment surface
# ---------------------------------------------------------------------------


def test_cli_build_launch_agent_pipeline(tmp_path, monkeypatch, capsys):
    monkeypatch.chdir(tmp_path)
    src = tmp_path / "train_dir"
    src.mkdir()
    (src / "main.py").write_text("print('trained')\n")

    assert cli_main(["build", "-sf", str(src), "-ep", "main.py",
                     "-o", str(tmp_path / "pkg.zip")]) == 0
    assert cli_main(["login", "acct", "--role", "client",
                     "--state_dir", str(tmp_path / "st")]) == 0
    # options precede the package; everything after it (flag-style included)
    # is handed to the job's entry point verbatim
    assert cli_main(["launch", "--jobs_dir", str(tmp_path / "jobs"),
                     str(tmp_path / "pkg.zip"), "--epochs", "2"]) == 0
    assert cli_main(["agent", "--once",
                     "--jobs_dir", str(tmp_path / "jobs"),
                     "--work_dir", str(tmp_path / "work"),
                     "--state_dir", str(tmp_path / "st")]) == 0
    out = capsys.readouterr().out
    assert "FINISHED" in out


@pytest.mark.slow
def test_reproduce_baselines_harness_fixture_run(tmp_path):
    """The published-baseline harness (tools/reproduce_baselines.py) runs a
    benchmark row end-to-end against the checked-in REAL-format fixture and
    reports data provenance honestly: real data for the fixture-staged row,
    synthetic (reproduces=null) without staging."""
    import json
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    fixture = os.path.join(repo, "tests", "fixtures", "stackoverflow")

    def run(*argv):
        p = subprocess.run(
            [sys.executable, os.path.join(repo, "tools",
                                          "reproduce_baselines.py"), *argv],
            capture_output=True, text=True, timeout=540,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        assert p.returncode == 0, p.stderr[-800:]
        return json.loads(p.stdout.strip().splitlines()[-1])

    real = run("--row", "stackoverflow_lr", "--cache-dir", fixture,
               "--rounds", "2")
    assert real["data"] == "real" and real["reproduces"] is None
    # the repo STAGES real MNIST (the t10k files at data_real/ — see
    # BASELINE.md): the default-cache run is real data under the disclosed
    # t10k-split protocol, never an unqualified reproduces claim
    staged = run("--row", "mnist_lr", "--rounds", "2",
                 "--cache-dir", os.path.join(repo, "data_real"))
    assert staged["data"] == "real"
    assert staged["protocol"] == "mnist_t10k_split"
    assert staged["reproduces"] is None
    assert staged["published_acc"] == 81.9
    # an explicitly-empty cache dir still degrades to synthetic, honestly
    synth = run("--row", "mnist_lr", "--rounds", "2",
                "--cache-dir", str(tmp_path))
    assert synth["data"] == "synthetic" and synth["reproduces"] is None
