"""One `master.main` job as a child process, driven from outside.

The way `chip_smoke.run_job` drives a job (copied here, where a PR
that claims a gain cannot change it): the master is started as the
user starts it (`python -m elasticdl_tpu.master.main --worker_backend
process`), polled through `GetSchedStats`, its worker logs and JSONL
metrics sink are read, and in the end the master's whole process group
is stopped. This process never imports jax: every chip belongs to a
worker.

Flags: the mix's `master_flags`, the configuration's minibatch, and
the program's defaults otherwise. `--records_per_task` is passed only
where the configuration's file states one (`"records_per_task"`, with
its reason): the default, 4096 records, is what A and B run.
"""

import datetime
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time

import grpc

from benchmark.harness import probe

_STAMP = r"(\d{4}-\d\d-\d\d \d\d:\d\d:\d\d,\d{3}) "
_BOOT = re.compile(
    _STAMP + r".*Worker \d+ boot: platform=(\S+) device_kind=(.+) chips=\[(.*)\]"
)
_DONE = re.compile(_STAMP + r".*Worker \d+ task \d+ done \(last loss ")
_DROPPED = re.compile(r"Task \d+ failed \d+ times, dropping")
DEFAULT_RECORDS_PER_TASK = 4096  # common/args.py


def when(stamp):
    return datetime.datetime.strptime(
        stamp, "%Y-%m-%d %H:%M:%S,%f"
    ).timestamp()


def read(path):
    try:
        with open(path, errors="replace") as f:
            return f.read()
    except OSError:
        return ""


def free_port():
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def cpu_seconds(pid):
    """utime + stime of one process, from /proc/<pid>/stat."""
    fields = read(f"/proc/{pid}/stat").rsplit(")", 1)[-1].split()
    if len(fields) < 13:
        return None
    return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")


class JobFailure(RuntimeError):
    pass


class Job:
    """`resolved` is `manifest.resolve`'s cell; `run_dir` holds the
    job's logs, metrics sink and probe directory."""

    def __init__(self, root, run_dir, resolved, data_dir, *, trace_secs=0.0,
                 extra_env=None):
        sizes, mix = resolved["sizes"], resolved["mix"]
        self.root = root
        self.run_dir = run_dir
        self.workers = int(mix["workers"])
        self.minibatch = int(sizes["minibatch_per_chip"])
        self.window = int(mix["master_flags"].get("local_updates", 0))
        stated = sizes.get("records_per_task")
        self.per_task = int(stated or DEFAULT_RECORDS_PER_TASK)
        self.setup_tasks = int(mix.get("setup_tasks", 2))
        self.log_dir = os.path.join(run_dir, "logs")
        self.probe_dir = os.path.join(run_dir, "probe")
        self.events_file = os.path.join(run_dir, "tb", "events.jsonl")
        self.master_log = os.path.join(run_dir, "master.log")
        self.port = free_port()
        os.makedirs(self.probe_dir)
        flags = {
            "model_zoo": os.path.relpath(resolved["config_dir"], root),
            "model_def": "zoo.custom_model",
            "minibatch_size": self.minibatch,
            "training_data_dir": data_dir,
            **({"records_per_task": self.per_task} if stated else {}),
            # epochs enough to outlast set-up and window at any speed
            "num_epochs": 1000000,
            "num_workers": self.workers,
            "worker_backend": "process",
            "port": self.port,
            "tensorboard_log_dir": os.path.join(run_dir, "tb"),
            **mix["master_flags"],
        }
        self.argv = [sys.executable, "-m", "elasticdl_tpu.master.main"]
        for key, value in flags.items():
            self.argv += [f"--{key}", str(value)]
        pythonpath = os.environ.get("PYTHONPATH")
        self.env = {
            **os.environ,
            "PYTHONPATH": root + (os.pathsep + pythonpath if pythonpath else ""),
            "EDL_WORKER_LOG_DIR": self.log_dir,
            "EDL_TPU_TB_BACKEND": "jsonl",  # the master's own sink
            probe.ENV_DIR: self.probe_dir,
            probe.ENV_TRACE_SECS: str(trace_secs),
            **(extra_env or {}),
        }
        self.master = None
        self.client = None
        self.last_rpc_error = None
        self.seen_pids = {}  # pid -> worker id, every worker ever seen
        self._events_pos = 0
        self._read_before = None
        self.events = []

    # -- life ---------------------------------------------------------------

    def start(self):
        with open(self.master_log, "wb") as logf:
            self.master = subprocess.Popen(
                self.argv, env=self.env, cwd=self.root, stdout=logf,
                stderr=logf, start_new_session=True,
            )

    def stats(self):
        """GetSchedStats, or None while the master boots; raises once
        the master is gone. The client is made once the port answers
        and dropped on an error: `RpcClient`'s circuit breaker would
        otherwise stay open for seconds after a boot's refused calls."""
        from elasticdl_tpu.rpc.client import RpcClient

        if self.master.poll() is not None:
            raise JobFailure(
                f"the master exited {self.master.returncode}:\n"
                + read(self.master_log)[-3000:]
            )
        try:
            if self.client is None:
                socket.create_connection(
                    ("localhost", self.port), timeout=0.5
                ).close()
                self.client = RpcClient(f"localhost:{self.port}")
            return self.client.call("GetSchedStats", {}, timeout=5.0)
        except (grpc.RpcError, OSError) as e:
            self.last_rpc_error = repr(e)
            if self.client is not None:
                self.client.close()
                self.client = None
            return None

    def stop(self):
        """SIGKILL the master's process group and wait until the
        master and every worker it ever started are gone."""
        if self.client is not None:
            self.client.close()
        if self.master is None:
            return
        self.worker_pids()
        try:
            os.killpg(self.master.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.master.wait()
        deadline = time.monotonic() + 30
        for pid in self.seen_pids:
            # orphans of a killed master are reaped by init
            while os.path.exists(f"/proc/{pid}") and time.monotonic() < deadline:
                state = read(f"/proc/{pid}/stat").rsplit(")", 1)[-1].split()
                if state and state[0] == "Z":
                    break
                try:
                    os.kill(pid, signal.SIGKILL)
                except ProcessLookupError:
                    break
                time.sleep(0.05)

    # -- what the job shows -------------------------------------------------

    def master_holds_tpu(self):
        return "libtpu" in read(f"/proc/{self.master.pid}/maps")

    def worker_pids(self):
        """{worker id: pid} of the live worker children of the master."""
        pids = {}
        for entry in os.listdir("/proc"):
            if not entry.isdigit():
                continue
            try:
                with open(f"/proc/{entry}/stat") as f:
                    ppid = int(f.read().rsplit(")", 1)[1].split()[1])
                if ppid != self.master.pid:
                    continue
                with open(f"/proc/{entry}/cmdline", "rb") as f:
                    argv = f.read().decode(errors="replace").split("\0")
            except (OSError, IndexError, ValueError):
                continue  # the process exited while we looked
            if probe.WORKER_MAIN in argv and "--worker_id" in argv:
                wid = int(argv[argv.index("--worker_id") + 1])
                pids[wid] = int(entry)
                self.seen_pids[int(entry)] = wid
        return pids

    def worker_logs(self):
        """{worker id: {"boot": {"at", "platform", "device_kind",
        "chips"} | None, "done": [time of each completed task]}}."""
        out = {}
        if not os.path.isdir(self.log_dir):
            return out
        for name in sorted(os.listdir(self.log_dir)):
            match = re.search(r"worker-(\d+)\.log$", name)
            if not match:
                continue
            text = read(os.path.join(self.log_dir, name))
            boot = _BOOT.search(text)
            out[int(match.group(1))] = {
                "boot": boot and {
                    "at": when(boot.group(1)),
                    "platform": boot.group(2),
                    "device_kind": boot.group(3),
                    "chips": [
                        int(c) for c in boot.group(4).split(",") if c.strip()
                    ],
                },
                "done": [when(m.group(1)) for m in _DONE.finditer(text)],
            }
        return out

    def dropped_tasks(self):
        return len(_DROPPED.findall(read(self.master_log)))

    def new_events(self):
        """The sink's lines since the last call, as dicts. Each gets
        `seen`: the benchmark's own clock before the previous read
        (None at the first) and after this one — the line was written
        between the two, which is what its `ts` is held to."""
        before = time.time()
        try:
            with open(self.events_file, "rb") as f:
                f.seek(self._events_pos)
                chunk = f.read()
        except OSError:
            chunk = b""
        seen = (self._read_before, time.time())
        self._read_before = before
        whole = chunk.rfind(b"\n") + 1  # a line still being written waits
        self._events_pos += whole
        fresh = [
            {**json.loads(line), "seen": seen}
            for line in chunk[:whole].splitlines() if line
        ]
        self.events += fresh
        return fresh

    def probe_records(self):
        """{pid: the probe's last record} of every worker that wrote one."""
        out = {}
        for name in os.listdir(self.probe_dir):
            if name.endswith(".json"):
                try:
                    with open(os.path.join(self.probe_dir, name)) as f:
                        record = json.load(f)
                except (OSError, ValueError):
                    continue
                out[record["pid"]] = record
        return out

    def drop_trace_latch(self):
        path = os.path.join(self.probe_dir, probe.LATCH)
        with open(path + ".tmp", "w") as f:
            f.write(repr(time.time()))
        os.replace(path + ".tmp", path)

    def tail(self, limit=3000):
        text = f"--- master.log\n{read(self.master_log)[-limit:]}\n"
        if os.path.isdir(self.log_dir):
            for name in sorted(os.listdir(self.log_dir)):
                text += f"--- {name}\n"
                text += read(os.path.join(self.log_dir, name))[-limit:] + "\n"
        return text
