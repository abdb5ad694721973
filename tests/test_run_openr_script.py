"""Deployment wrapper smoke: scripts/run_openr.sh launches the real
daemon via the reference-style env-file surface (the analogue of
/root/reference/openr/scripts/run_openr.sh + openr.service)."""

import os
import signal
import socket
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "run_openr.sh")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def wait_until(pred, timeout=30.0, interval=0.2):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


class TestRunOpenrScript:
    def test_launch_and_ctrl_roundtrip(self, tmp_path):
        port = _free_port()
        # node overrides go in the SYSCONFIG env file (the reference's
        # /etc/sysconfig/openr mechanism), not the process env
        sysconfig = tmp_path / "openr.sysconfig"
        sysconfig.write_text(
            f'NODE_NAME="smoke-node"\n'
            f'OPENR_CTRL_PORT={port}\n'
            f'CONFIG_STORE_FILEPATH="{tmp_path / "store.json"}"\n'
            f'ENABLE_NETLINK_FIB_HANDLER=false\n'
            f'ENABLE_WATCHDOG=false\n'
            f'DRYRUN=true\n'
        )
        env = dict(
            os.environ,
            SYSCONFIG=str(sysconfig),
            OPENR=f"{sys.executable} -m openr_tpu.main",
            JAX_PLATFORMS="cpu",
        )
        proc = subprocess.Popen(
            ["bash", SCRIPT],
            env=env,
            cwd=REPO,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        try:
            def ctrl_up():
                if proc.poll() is not None:
                    return True  # died: fail below with output
                try:
                    s = socket.create_connection(
                        ("127.0.0.1", port), timeout=1
                    )
                    s.close()
                    return True
                except OSError:
                    return False

            # the daemon imports jax before it listens: beside five other
            # workers that has taken longer than the default 30 s
            assert wait_until(ctrl_up, timeout=90.0), "ctrl port never opened"
            if proc.poll() is not None:
                out = proc.stdout.read().decode(errors="replace")
                pytest.fail(f"daemon exited rc={proc.returncode}:\n{out}")
            # the launched daemon answers BOTH ctrl codecs
            from openr_tpu.ctrl.server import CtrlClient
            from openr_tpu.ctrl.thrift_ctrl import ThriftCtrlClient

            client = CtrlClient("127.0.0.1", port)
            try:
                assert client.call("get_my_node_name") == "smoke-node"
            finally:
                client.close()
            tclient = ThriftCtrlClient("127.0.0.1", port)
            try:
                assert tclient.call("getMyNodeName") == "smoke-node"
            finally:
                tclient.close()
        finally:
            os.killpg(proc.pid, signal.SIGTERM)
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait(timeout=10)

    def test_refuses_without_node_name(self, tmp_path):
        sysconfig = tmp_path / "sc"
        sysconfig.write_text('NODE_NAME="localhost"\n')
        env = dict(os.environ, SYSCONFIG=str(sysconfig))
        proc = subprocess.run(
            ["bash", SCRIPT], env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            timeout=30,
        )
        assert proc.returncode != 0
        assert b"hostname" in proc.stdout.lower()
