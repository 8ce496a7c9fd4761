"""Multi-host launch story (reference tools/launch.py:32-79 ->
dmlc_tracker ssh launcher): the ssh mode builds per-rank remote
commands with coordinator/rank env propagation, round-robins the
hostfile, and reuses the local launcher's failure detection.

No sshd runs in this image, so a loopback shim stands in for ssh: it
logs the (host, remote-command) pair and executes the command locally
through `sh -c` — exactly what sshd would do — so the whole launcher
path (env propagation, quoting, cd, rendezvous, collectives) executes
for real across 2 processes.
"""
import os
import signal
import stat
import subprocess
import sys

import pytest

from dist_util import REPO

WORKER = r"""
import os, sys
sys.path.insert(0, %(repo)r)
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import mxnet_tpu as mx

kv = mx.kv.create("dist_sync")
rank, nw = kv.rank, kv.num_workers
assert rank == int(os.environ["MXTPU_WORKER_RANK"]), "rank env mismatch"
assert nw == 2, nw
# exact push/pull arithmetic across the group
v = mx.nd.array(np.full((4,), float(rank + 1), dtype=np.float32))
kv.init(9, mx.nd.zeros((4,)))
kv.push(9, v)
out = mx.nd.zeros((4,))
kv.pull(9, out)
np.testing.assert_allclose(out.asnumpy(), np.full((4,), 3.0))
print("SSH_WORKER_OK rank=" + str(rank) + " cwd=" + os.getcwd())
"""


def test_ssh_launcher_loopback(tmp_path):
    shim = tmp_path / "fake_ssh"
    log = tmp_path / "ssh_log.txt"
    seen = tmp_path / "secret_seen.txt"
    shim.write_text(
        "#!/bin/sh\n"
        "# drop '-tt' and '-o opt' args, record host + command, run locally\n"
        "while [ \"$1\" = \"-o\" ] || [ \"$1\" = \"-tt\" ]; do\n"
        "  if [ \"$1\" = \"-o\" ]; then shift 2; else shift; fi\n"
        "done\n"
        "host=\"$1\"; shift\n"
        "printf '%s\\t%s\\n' \"$host\" \"$*\" >> " + str(log) + "\n"
        "# the staged secret as a worker host sees it while the job runs\n"
        "f=$(printf '%s' \"$*\" | sed -n "
        "'s/.*MXTPU_PS_SECRET_FILE=\\([^ ]*\\).*/\\1/p')\n"
        "printf '%s\\t%s\\t%s\\n' \"$f\" \"$(stat -c %a \"$f\")\" "
        "\"$(cat \"$f\")\" >> " + str(seen) + "\n"
        "exec /bin/sh -c \"$*\"\n")
    shim.chmod(shim.stat().st_mode | stat.S_IEXEC)

    hostfile = tmp_path / "hosts"
    hostfile.write_text("host-a  # first pod host\nhost-b\n")

    workdir = tmp_path / "job"
    workdir.mkdir()
    script = workdir / "worker.py"
    script.write_text(WORKER.replace("%(repo)r", repr(REPO)))

    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = REPO
    env["MXTPU_PS_SECRET"] = "hunter2-cluster-token"
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", "--launcher", "ssh", "-H", str(hostfile),
         "--ssh-cmd", str(shim), "--coordinator", "127.0.0.1:23474",
         "--sync-dir", str(workdir),
         sys.executable, "worker.py"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=str(tmp_path), start_new_session=True)
    try:
        stdout, stderr = proc.communicate(timeout=300)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        stdout, stderr = proc.communicate()
        raise
    if proc.returncode != 0 and "SSH_WORKER_OK" not in stdout \
            and "distributed" in (stderr or "").lower():
        pytest.skip("jax.distributed unavailable: %s" % stderr[-200:])
    assert proc.returncode == 0, (stdout[-1000:], stderr[-2000:])
    assert stdout.count("SSH_WORKER_OK") == 2, stdout

    lines = log.read_text().strip().splitlines()
    hosts = [l.split("\t")[0] for l in lines]
    assert sorted(hosts) == ["host-a", "host-b"], hosts  # round-robin
    for l in lines:
        cmd = l.split("\t")[1]
        assert "MXTPU_COORDINATOR=127.0.0.1:23474" in cmd
        assert "MXTPU_NUM_WORKERS=2" in cmd
        assert "PYTHONPATH=" in cmd          # forwarded env
        assert "cd %s" % workdir in cmd      # shared-dir assumption
    ranks = sorted(int(l.split("MXTPU_WORKER_RANK=")[1].split()[0])
                   for l in lines)
    assert ranks == [0, 1]

    # the PS shared secret must never ride the (world-readable) ssh
    # argv: it is staged as a 0600 file in the job dir and only its
    # PATH is forwarded (launch.py round-4 hardening)
    for l in lines:
        assert "hunter2-cluster-token" not in l, "secret leaked to argv"
        assert "MXTPU_PS_SECRET_FILE=" in l.split("\t")[1]
    # while the job ran, both hosts saw ONE file in the job dir, named
    # for this job alone (pid.time suffix: overlapping jobs in one
    # shared dir cannot clobber each other's secret), mode 0600, holding
    # the secret
    rows = [l.split("\t") for l in seen.read_text().strip().splitlines()]
    assert len(rows) == 2, rows
    paths = {r[0] for r in rows}
    assert len(paths) == 1, paths
    (path,) = paths
    assert os.path.dirname(path) == str(workdir)
    name = os.path.basename(path).split(".")
    assert name[:2] == ["", "mxtpu_ps_secret"], path
    assert name[2] == str(proc.pid) and name[3].isdigit(), path
    for _, mode, text in rows:
        assert mode == "600"
        assert text == "hunter2-cluster-token"
    # and it does not outlive the job (PR 1): a later reader of the
    # shared dir finds no HMAC key
    assert not os.path.exists(path)
    assert list(workdir.glob(".mxtpu_ps_secret.*")) == []
