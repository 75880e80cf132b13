"""The fused walk is one program: one dispatch and one device sync per run.

On the 8-virtual-device `coexec` x `lane` mesh, where the split is active,
a fused run (`PlanExecutor.run(fused=True)`) of a residual DAG, a conv
graph with a pool boundary, a head-split attention plan (a typed-axis
singleton) and a decoder block with exclusive attention must

  * call `jax.block_until_ready` exactly once;
  * execute exactly one XLA program, the walk: under the profiler the
    run's host events hold one executable launch, inside the walk's
    dispatch, and no other jitted function or transfer outside it;
  * return the per-node walk's output, byte for byte.

On the CPU each segment's published output is fenced (`segments._fence`),
which is what keeps those bytes equal; on the chip the fence is the
identity, and XLA may fuse across segments.  The same checks run with the
chip's branch swapped in on the CPU, where the output must match the
per-node walk within the oracle tolerance the segment tests use (the
decoder's then differs from it in the last bits).
"""
import os
import subprocess
import sys
import textwrap

import pytest

_PROG = textwrap.dedent("""
    import os, sys, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    from pathlib import Path
    import jax, numpy as np
    from repro.core.coexec import coexec_mesh
    from repro.core.partitioner import PartitionDecision
    from repro.core.types import ConvOp, LinearOp
    from repro.graph.frontends import from_model
    from repro.graph.ir import Graph, Node, from_units
    from repro.runtime.executor import PlanExecutor
    from repro.runtime.plan import (CoexecPlan, PlanProvenance,
                                    build_graph_schedule, segments_json)

    def residual():
        nodes = [Node(id="embed", kind="linear", op=LinearOp(3, 24, 24))]
        prev = "embed"
        for b in range(2):
            nodes.append(Node(id=f"b{b}.u", kind="linear",
                              op=LinearOp(3, 24, 24), inputs=(prev,)))
            nodes.append(Node(id=f"b{b}.v", kind="linear",
                              op=LinearOp(3, 24, 24), inputs=(f"b{b}.u",)))
            nodes.append(Node(id=f"b{b}.r", kind="add",
                              inputs=(prev, f"b{b}.v")))
            prev = f"b{b}.r"
        return Graph(nodes)

    def pool():
        return from_units([("conv", ConvOp(8, 8, 8, 16, 3, 1)),
                           ("conv", ConvOp(8, 8, 16, 16, 3, 1)),
                           ("pool", 4 * 4 * 4 * 16),
                           ("conv", ConvOp(4, 4, 16, 24, 3, 1)),
                           ("linear", LinearOp(1, 4 * 4 * 24, 32))])

    def head_split():
        return from_model("tiny_decoder", cache_len=512)

    def decoder():
        return from_model("tiny_decoder")

    def forced(g, head_split):
        decisions, opaque = {}, {}
        for n in g:
            if n.kind in ("linear", "conv"):
                c = n.op.C_out
                decisions[n.id] = PartitionDecision(
                    op=n.op, c_cpu=c // 4, c_gpu=c - c // 4,
                    pred_cpu_us=1.0, pred_gpu_us=1.0, pred_total_us=2.0)
            elif n.kind == "attention" and not head_split:
                opaque[n.id] = 1.0
            elif n.kind == "attention":
                decisions[n.id] = PartitionDecision(
                    op=n.op, c_cpu=n.op.H // 2, c_gpu=n.op.H // 2,
                    pred_cpu_us=1.0, pred_gpu_us=1.0, pred_total_us=2.0,
                    axis="head")
        prov = PlanProvenance(
            device="moto2022", threads=3, mechanism="svm_poll", step=8,
            seed=1, network_fingerprint=g.fingerprint(),
            predictor_checksum="")
        return CoexecPlan(
            provenance=prov,
            schedule=build_graph_schedule(g, decisions, opaque),
            graph_json=None if g.is_unit_chain() else g.to_json(),
            segments=segments_json(g, decisions))

    case, fence = sys.argv[1], sys.argv[2]
    if fence == "unfenced":
        # the chip's branch of the fence: the identity
        import repro.runtime.segments as segments
        segments._fence = lambda v: v
    g = {"residual": residual, "pool": pool, "head_split": head_split,
         "decoder": decoder}[case]()
    exe = PlanExecutor(forced(g, case == "head_split"),
                       mesh=coexec_mesh(jax.devices()))
    assert exe.split_capable
    x = exe.input_template()
    y_u, rep_u = exe.run(x)
    exe.run(x, fused=True, warmup=True)
    progs = exe.walk_program(tuple(x.shape)).segments
    assert len(progs) > 1, case
    if case == "head_split":
        assert any(p.kind != "fused" and set(p.modes.values()) == {"coexec"}
                   for p in progs), "no typed-axis singleton"
    if case == "pool":
        assert any(p.kind == "pool" for p in progs)

    syncs = []
    block = jax.block_until_ready

    def counted(v):
        syncs.append(1)
        return block(v)

    jax.block_until_ready = counted
    d = Path(tempfile.mkdtemp())
    with jax.profiler.trace(str(d)):
        y_f, rep_f = exe.run(x, fused=True)
    jax.block_until_ready = block
    assert len(syncs) == 1 == rep_f.sync_points, (case, syncs)
    assert rep_f.segments == len(progs) and rep_u.sync_points > 1

    (path,) = d.glob("**/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for plane in data.planes if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if not e.name.startswith("$")]
    (run,) = [e for e in events if e[0] == "repro.exec.run"]
    inside = [e for e in events if run[1] <= e[1] and e[2] <= run[2]]
    walks = [e for e in inside if e[0] == "PjitFunction(walk)"]
    assert walks, case

    def in_walk(e):
        return any(w[1] <= e[1] and e[2] <= w[2] for w in walks)

    launches = [e for e in inside if e[0].endswith("Executable::Execute")]
    assert len(launches) == 1 and in_walk(launches[0]), (case, launches)
    eager = [e[0] for e in inside
             if (e[0].startswith("PjitFunction(") or "DevicePut" in e[0])
             and not in_walk(e)]
    assert not eager, (case, eager)
    others = {e[0] for e in inside if e[0].startswith("PjitFunction(")}
    assert others == {"PjitFunction(walk)"}, (case, others)

    if fence == "fenced":
        assert np.asarray(y_f).tobytes() == np.asarray(y_u).tobytes(), case
    else:
        np.testing.assert_allclose(np.asarray(y_f), np.asarray(y_u),
                                   rtol=2e-5, atol=2e-5, err_msg=case)
    print("WALK_OK", case, fence, len(progs), "segments")
""")

CASES = ["residual", "pool", "head_split", "decoder"]


def _run(case: str, fence: str) -> None:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _PROG, case, fence], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert f"WALK_OK {case} {fence}" in out.stdout


@pytest.mark.parametrize("case", CASES)
def test_fused_run_is_one_program_and_one_sync(case):
    _run(case, "fenced")


@pytest.mark.parametrize("case", CASES)
def test_unfenced_walk_matches_the_per_node_walk(case):
    """The walk as the chip runs it, with no fence between segments: one
    program, one sync, and the per-node walk's output within 2e-5."""
    _run(case, "unfenced")
