"""``lo-cluster`` — one-command pod bring-up with restart-on-failure.

The reference deploys with ``bash run.sh``: build, push to a local
registry, ``docker stack deploy`` of 17 services, every one under
Swarm's ``restart_policy: on-failure`` (reference run.sh:1-130,
docker-compose.yml:3-6). This is the TPU-native equivalent for one
machine (or one TPU-pod host group reachable from it): spawn the
coordinator plus N-1 workers as ``lo-server`` processes and supervise
them.

Restart semantics are POD-level, not per-process: a JAX multi-host pod
is all-or-nothing — when one member dies, jax's coordination service
fatally exits the survivors anyway (and a half-replaced pod could
never rejoin a live jit). So on any member's non-zero exit the
supervisor tears the whole pod down and re-forms it; checkpointed
trains resume from their latest checkpoint step and the boot requeue
replays unfinished jobs (docs/DEPLOY.md "Failure semantics"). Clean
exits (code 0, e.g. after SIGTERM drain) do not restart — the Swarm
``on-failure`` contract.

    lo-cluster --hosts 4 --port 8080 --home /shared/lo

For multi-machine deployments run one ``lo-server`` per machine under
your scheduler's restart policy instead (k8s/systemd examples in
docs/DEPLOY.md); ``deploy/docker-compose.yml`` packages the same
layout for container platforms.
"""

from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys
import time
from typing import List, Optional


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class PodSupervisor:
    """Spawn + supervise one pod's member processes."""

    def __init__(self, hosts: int, port: int, home: str,
                 coordinator_port: Optional[int] = None,
                 rest_host: str = "127.0.0.1",
                 max_restarts: int = 5,
                 restart_window: float = 300.0,
                 backoff: float = 1.0,
                 extra_env: Optional[dict] = None):
        self.hosts = hosts
        self.port = port
        self.home = home
        self.coordinator_port = coordinator_port or _free_port()
        self.rest_host = rest_host
        self.max_restarts = max_restarts
        self.restart_window = restart_window
        self.backoff = backoff
        self.extra_env = dict(extra_env or {})
        self.procs: List[subprocess.Popen] = []
        self._restart_times: List[float] = []
        self._stopping = False
        os.makedirs(os.path.join(home, "logs"), exist_ok=True)

    # ------------------------------------------------------------------
    def _spawn_member(self, host_id: int) -> subprocess.Popen:
        env = dict(os.environ)
        env.update(self.extra_env)
        log_path = os.path.join(self.home, "logs",
                                f"host{host_id}.log")
        log = open(log_path, "ab")
        cmd = [sys.executable, "-m", "learningorchestra_tpu",
               "--home", self.home,
               "--host", self.rest_host, "--port", str(self.port),
               "--coordinator",
               f"{self.rest_host}:{self.coordinator_port}",
               "--num-hosts", str(self.hosts),
               "--host-id", str(host_id)]
        proc = subprocess.Popen(cmd, stdout=log, stderr=log, env=env)
        log.close()  # the child holds its own fd
        return proc

    def start(self) -> None:
        print(f"lo-cluster: forming pod of {self.hosts} "
              f"(coordinator 127.0.0.1:{self.coordinator_port}, REST "
              f"http://{self.rest_host}:{self.port}, logs "
              f"{self.home}/logs/)", flush=True)
        self.procs = [self._spawn_member(i) for i in range(self.hosts)]

    def _teardown(self, sig=signal.SIGTERM,
                  grace: float = 75.0) -> None:
        # the SIGTERM grace must exceed lo-server's own 60s in-flight
        # job drain, or a clean stop SIGKILLs members mid-drain
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.send_signal(sig)
                except OSError:
                    pass
        deadline = time.monotonic() + grace
        for p in self.procs:
            timeout = max(0.1, deadline - time.monotonic())
            try:
                p.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()

    def _budget_exhausted(self) -> bool:
        now = time.monotonic()
        self._restart_times = [t for t in self._restart_times
                               if now - t < self.restart_window]
        return len(self._restart_times) >= self.max_restarts

    def supervise(self) -> int:
        """Block, restarting the pod on member failure. Returns an
        exit code (0 = clean shutdown)."""

        def _stop(signum, frame):  # noqa: ARG001
            self._stopping = True

        try:
            signal.signal(signal.SIGTERM, _stop)
            signal.signal(signal.SIGINT, _stop)
        except ValueError:
            pass  # not the main thread (embedder drives _stopping)
        while True:
            if self._stopping:
                print("lo-cluster: draining pod", flush=True)
                self._teardown()
                return 0
            failed = [i for i, p in enumerate(self.procs)
                      if p.poll() not in (None, 0)]
            clean = [i for i, p in enumerate(self.procs)
                     if p.poll() == 0]
            if clean and not failed:
                # coordinator drained cleanly (operator stop) — treat
                # as pod shutdown, stop the rest
                print("lo-cluster: member exited cleanly, stopping "
                      "pod", flush=True)
                self._teardown()
                return 0
            if failed:
                if self._budget_exhausted():
                    print(f"lo-cluster: restart budget exhausted "
                          f"({self.max_restarts} restarts in "
                          f"{self.restart_window:.0f}s) — giving up",
                          flush=True)
                    self._teardown(signal.SIGKILL, grace=5.0)
                    return 1
                codes = {i: self.procs[i].poll() for i in failed}
                print(f"lo-cluster: member(s) {codes} failed — "
                      f"re-forming pod", flush=True)
                # pod-level restart: survivors are doomed (jax's
                # coordination service exits them) and cannot rejoin
                self._teardown(signal.SIGKILL, grace=10.0)
                self._restart_times.append(time.monotonic())
                time.sleep(self.backoff)
                # a fresh coordinator port avoids TIME_WAIT collisions
                self.coordinator_port = _free_port()
                self.start()
            time.sleep(0.5)


def main(argv=None) -> int:
    import argparse

    parser = argparse.ArgumentParser(
        description="one-command learningOrchestra-TPU pod bring-up "
                    "with restart-on-failure (run.sh parity)")
    parser.add_argument("--hosts", type=int, default=1,
                        help="pod size (1 coordinator + N-1 workers)")
    parser.add_argument("--port", type=int, default=8080,
                        help="REST port on the coordinator")
    parser.add_argument("--host", default="127.0.0.1",
                        help="bind/coordinator address")
    parser.add_argument("--home", default=os.environ.get(
        "LO_HOME", "./.lo_store"), help="shared storage root")
    parser.add_argument("--coordinator-port", type=int, default=None)
    parser.add_argument("--max-restarts", type=int, default=5,
                        help="pod restarts allowed per window before "
                             "giving up")
    parser.add_argument("--restart-window", type=float, default=300.0)
    args = parser.parse_args(argv)

    sup = PodSupervisor(hosts=args.hosts, port=args.port,
                        home=args.home,
                        coordinator_port=args.coordinator_port,
                        rest_host=args.host,
                        max_restarts=args.max_restarts,
                        restart_window=args.restart_window)
    sup.start()
    return sup.supervise()


if __name__ == "__main__":
    sys.exit(main())
