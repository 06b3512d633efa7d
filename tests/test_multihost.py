"""Two-process multi-host smoke test over jax.distributed on CPU.

The reference's multi-node path is only testable with real machines
(`SURVEY.md` §4: no automated distributed test exists there). Here the
``--coordinator/--num-hosts/--host-id`` bootstrap (cli.maybe_init_distributed)
is exercised for real: two OS processes join one jax.distributed job on
localhost, see the global device picture, and run a psum across processes.
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHILD = textwrap.dedent(
    """
    import argparse, sys
    import jax
    jax.config.update("jax_platforms", "cpu")

    from dllama_tpu.cli import build_parser, maybe_init_distributed

    argv = sys.argv[1:]
    args = build_parser().parse_args(argv)
    idx = maybe_init_distributed(args)
    assert idx == args.host_id, (idx, args.host_id)
    assert jax.process_count() == args.num_hosts
    assert jax.device_count() == args.num_hosts  # one cpu device per process
    assert len(jax.local_devices()) == 1

    # a real cross-process collective: every process contributes its id + 1
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.experimental import multihost_utils

    total = multihost_utils.process_allgather(np.asarray([idx + 1]))
    assert int(total.sum()) == sum(range(1, args.num_hosts + 1)), total

    # what load_engine and /stats ask on every host: the whole job's devices,
    # allocator statistics only from this process's own (memory_stats()
    # raises for a device another host addresses)
    from dllama_tpu.runtime import device
    info = device.device_info()
    assert info["count"] == args.num_hosts, info
    assert len(info["bytes_in_use"]) == info["count"], info
    print(f"HOST {idx} OK", flush=True)
    """
)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_distributed_bootstrap(tmp_path):
    port = _free_port()
    child_py = tmp_path / "child.py"
    child_py.write_text(CHILD)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)  # one device per process: a real 2-host shape
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    def spawn(host_id):
        return subprocess.Popen(
            [
                sys.executable, str(child_py), "generate",
                "--model", "unused.m", "--tokenizer", "unused.t",
                "--coordinator", f"127.0.0.1:{port}",
                "--num-hosts", "2", "--host-id", str(host_id),
            ],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        )

    procs = [spawn(0), spawn(1)]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-host bootstrap deadlocked")
        outs.append((p.returncode, out, err))
    for i, (rc, out, err) in enumerate(outs):
        assert rc == 0, f"host {i} failed:\n{err}\n{out}"
        assert f"HOST {i} OK" in out
