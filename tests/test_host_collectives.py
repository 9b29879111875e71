"""Gloo-equivalent host collectives (reference:
fleet/gloo_wrapper.h:106 Barrier/AllReduce + HdfsStore rendezvous) and
dataset global shuffle across 2 real processes."""
import pytest

pytestmark = pytest.mark.dist

import os
import socket
import subprocess
import sys
import textwrap

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_DIR)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _env(extra):
    from childenv import cpu_child_env

    return cpu_child_env(extra)


def test_host_collectives_two_processes():
    port = _free_port()
    script = textwrap.dedent("""
        import sys, numpy as np
        sys.path.insert(0, %r)
        from paddle_tpu.distributed.host_collectives import \\
            HostCollectiveGroup
        rank = int(sys.argv[1])
        g = HostCollectiveGroup(rank, 2, "127.0.0.1:%d")
        g.barrier()
        s = g.all_reduce(np.asarray([1.0 + rank, 2.0]), op="sum")
        print("SUM", s.tolist())
        parts = g.all_gather(np.asarray([rank * 10]))
        print("GATHER", [int(p[0]) for p in parts])
        b = g.broadcast(np.asarray([42 + rank]), root=0)
        print("BCAST", int(b[0]))
        g.barrier()
        # leak regression: every collective's blobs must be released
        # once both ranks fetched. rank1 signals its last fetch is done
        # via a point-to-point key (hc_take pops it), THEN rank0 reads
        # the store stats — deterministic, no sleep.
        if rank == 1:
            g.put("drained", np.ones((1,), np.int8))
        else:
            g.take("drained")
            print("STATS", g.store_stats())
        g.shutdown()
    """ % (_REPO, port))
    procs = [subprocess.Popen([sys.executable, "-c", script, str(r)],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True,
                              env=_env({}))
             for r in range(2)]
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=120)
        assert p.returncode == 0, out
        outs.append(out)
    for out in outs:
        assert "SUM [3.0, 4.0]" in out, out
        assert "GATHER [0, 10]" in out, out
        assert "BCAST 42" in out, out
    # rank0 printed the store stats after both ranks drained
    assert "STATS (0, 0, 0)" in outs[0] + outs[1], outs


def test_dataset_global_shuffle_two_processes(tmp_path):
    """Each rank loads a DISJOINT file; after global_shuffle the union
    is exactly partitioned across ranks (records exchanged, none lost
    or duplicated)."""
    port = _free_port()
    # slot format: one uint64 id slot, one value per line (MultiSlot)
    for r in range(2):
        with open(tmp_path / ("part-%d.txt" % r), "w") as f:
            for i in range(4):
                rid = r * 100 + i
                f.write("1 %d\n" % rid)
    script = textwrap.dedent("""
        import os, sys, numpy as np
        sys.path.insert(0, %r)
        rank = int(sys.argv[1])
        os.environ["PADDLE_TRAINER_ID"] = str(rank)
        os.environ["PADDLE_TRAINERS_NUM"] = "2"
        os.environ["PADDLE_TRAINER_ENDPOINTS"] = \\
            "127.0.0.1:%d,127.0.0.1:1"
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import framework
        with framework.program_guard(framework.Program(),
                                     framework.Program()):
            with framework.unique_name_guard():
                v = fluid.layers.data(name="id", shape=[1],
                                      dtype="int64")
                ds = fluid.InMemoryDataset()
                ds.set_batch_size(1)
                ds.set_use_var([v])
                ds.set_filelist([sys.argv[2]])
                ds.load_into_memory()
                ds.global_shuffle()
                ids = sorted(int(ex[0][0][0]) for ex in ds._examples)
                print("IDS", ids)
    """ % (_REPO, port - 1))
    procs = [subprocess.Popen(
        [sys.executable, "-c", script, str(r),
         str(tmp_path / ("part-%d.txt" % r))],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=_env({})) for r in range(2)]
    id_sets = []
    for p in procs:
        out, _ = p.communicate(timeout=180)
        assert p.returncode == 0, out
        line = [ln for ln in out.splitlines()
                if ln.startswith("IDS")][0]
        id_sets.append(set(eval(line[4:])))
    union = id_sets[0] | id_sets[1]
    assert union == {0, 1, 2, 3, 100, 101, 102, 103}, id_sets
    assert not (id_sets[0] & id_sets[1]), id_sets
    assert len(id_sets[0]) == len(id_sets[1]) == 4
