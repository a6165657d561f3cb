"""`repro.compile` / `repro.compiler`: the unified compiler-pipeline API.

Contract under test (ISSUE 4 acceptance bar):

  * one entry point — ``repro.compile(graph, machine, backend=...)`` —
    returns a `Deployment` whose `run` is bit-exact vs ``reference_forward``
    on every registered backend;
  * the staged pass pipeline records inspectable per-stage artifacts and
    timing, and enforces deadlines at the wcet stage;
  * `Deployment.save`/`load` round-trips bit-exactly (outputs AND WCET
    bound) and *refuses* stale artifacts: wrong machine fingerprint, wrong
    graph signature, corrupt payloads;
  * the backend registry accepts third-party backends by name;
  * `repro.core.clear_program_cache` clears the deployment cache too.
"""

import dataclasses
import zipfile

import numpy as np
import pytest

import repro
from repro.compiler import (ArtifactError, BackendError, DeadlineError,
                            Deployment, PipelineError, TasksetDeployment,
                            clear_deployment_cache, get_backend,
                            list_backends, register_backend,
                            unregister_backend)
from repro.core import (clear_program_cache, cnn, init_params,
                        reference_forward)
from repro.core.graph import Graph, eltwise
from repro.core.taskset import NetworkSpec
from repro.hw import scaled_paper_machine

HW = scaled_paper_machine(4)


def _graph_and_input(seed=0):
    g = cnn.small_cnn()
    x = np.random.default_rng(seed).integers(
        -64, 64, (32, 32, 3)).astype(np.int8)
    return g, x


# -- compile + run -----------------------------------------------------------

@pytest.mark.parametrize("backend", ["numpy", "jax", "pallas"])
def test_compile_run_bit_exact(backend):
    """repro.compile(...).run(x) == reference_forward on every backend."""
    g, x = _graph_and_input()
    params = init_params(g, seed=1)
    dep = repro.compile(g, HW, backend=backend, params=params)
    ref = reference_forward(g, params, {"input": x})
    out = dep.run(x)
    for t in g.outputs:
        assert np.array_equal(ref[t], out[t])
    # per-call backend override works without recompiling
    out2 = dep.run({"input": x}, backend="numpy")
    for t in g.outputs:
        assert np.array_equal(ref[t], out2[t])


def test_compile_run_batched():
    g, _ = _graph_and_input()
    params = init_params(g, seed=2)
    dep = repro.compile(g, HW, backend="jax", params=params)
    xb = np.random.default_rng(3).integers(
        -64, 64, (3, 32, 32, 3)).astype(np.int8)
    out = dep.run(xb, batched=True)
    for b in range(3):
        ref = reference_forward(g, params, {"input": xb[b]})
        for t in g.outputs:
            assert np.array_equal(ref[t], out[t][b])


def test_pipeline_stages_recorded():
    """Per-stage telemetry + inspectable artifacts for the full sequence."""
    g, _ = _graph_and_input()
    dep = repro.compile(g, HW, use_cache=False)
    assert [s.name for s in dep.stages] == [
        "quantize", "partition", "map", "schedule", "wcet", "lower",
        "verify"]
    assert dep.artifacts["verify"].ok
    assert all(s.duration_s >= 0 for s in dep.stages)
    assert all(s.summary for s in dep.stages)
    assert len(dep.artifacts["partition"]) > 0          # subtasks
    assert dep.artifacts["map"].num_cores == 4
    assert dep.artifacts["wcet"].wcet_total_s == dep.wcet_bound_s
    assert dep.artifacts["quantize"]["missing_filled"]  # synthesized params


def test_compile_synthesizes_partial_params():
    """A partial params dict compiles; provided entries are baked verbatim."""
    g, x = _graph_and_input()
    full = init_params(g, seed=4)
    partial = {k: v for i, (k, v) in enumerate(sorted(full.items()))
               if i % 2 == 0}
    dep = repro.compile(g, HW, backend="numpy", params=partial,
                        use_cache=False)
    baked = dep.artifacts["quantize"]["params"]
    for k, v in partial.items():
        assert baked[k] is v
    ref = reference_forward(g, baked, {"input": x})
    out = dep.run(x)
    for t in g.outputs:
        assert np.array_equal(ref[t], out[t])


def test_deadline_enforced():
    g, _ = _graph_and_input()
    dep = repro.compile(g, HW, use_cache=False)        # learn the bound
    with pytest.raises(DeadlineError):
        repro.compile(g, HW, deadline=dep.wcet_bound_s / 10,
                      use_cache=False)
    # a feasible deadline compiles fine
    ok = repro.compile(g, HW, deadline=dep.wcet_bound_s * 2,
                       use_cache=False)
    assert ok.wcet_bound_s <= dep.wcet_bound_s * 2
    # the deadline is re-enforced on cache hits too
    cached = repro.compile(g, HW)
    assert cached.wcet_bound_s > 0
    with pytest.raises(DeadlineError):
        repro.compile(g, HW, deadline=cached.wcet_bound_s / 10)


def test_analysis_only_graph_refuses_lowering():
    g = Graph("mul")
    g.add_tensor("x", (4, 8), "int8", is_input=True)
    eltwise(g, "m", "mul", ["x", "x"])
    g.validate()
    with pytest.raises(PipelineError):
        repro.compile(g, HW, use_cache=False)


def test_compile_rejects_garbage():
    with pytest.raises(TypeError):
        repro.compile(42, HW)
    with pytest.raises(TypeError):
        repro.compile([], HW)


# -- caching -----------------------------------------------------------------

def test_deployment_cache_and_clear():
    clear_program_cache()
    g1, _ = _graph_and_input()
    g2 = cnn.small_cnn()                               # same signature
    params = init_params(g1, seed=5)
    d1 = repro.compile(g1, HW, params=params)
    d2 = repro.compile(g2, HW, params=params)          # hit
    assert d1 is d2
    d3 = repro.compile(g1, HW, params=params, backend="numpy")  # miss
    assert d3 is not d1
    hw2 = dataclasses.replace(HW, wcet_margin=HW.wcet_margin * 2)
    d4 = repro.compile(g1, hw2, params=params)         # machine miss
    assert d4 is not d1
    # clear_program_cache() clears the deployment cache through the hook
    clear_program_cache()
    d5 = repro.compile(g1, HW, params=params)
    assert d5 is not d1
    clear_deployment_cache()


# -- backend registry --------------------------------------------------------

def test_unknown_backend_fails_fast():
    g, _ = _graph_and_input()
    with pytest.raises(BackendError):
        repro.compile(g, HW, backend="nope")
    dep = repro.compile(g, HW, use_cache=False)
    with pytest.raises(BackendError):
        dep.run(np.zeros((32, 32, 3), np.int8), backend="nope")
    with pytest.raises(BackendError):
        dep.with_backend("nope")


def test_third_party_backend_pluggable():
    """register_backend makes a new name compilable and runnable; the
    default batched factory loops the single runner."""
    calls = {"n": 0}

    def make_single(prog, options):
        inner = get_backend("numpy").single(prog, options)

        def run(inputs):
            calls["n"] += 1
            return inner(inputs)
        return run

    register_backend("test_custom", single=make_single)
    try:
        assert "test_custom" in list_backends()
        g, x = _graph_and_input()
        params = init_params(g, seed=6)
        dep = repro.compile(g, HW, backend="test_custom", params=params,
                            use_cache=False)
        ref = reference_forward(g, params, {"input": x})
        out = dep.run(x)
        for t in g.outputs:
            assert np.array_equal(ref[t], out[t])
        assert calls["n"] == 1
        xb = np.stack([x, x])
        outb = dep.run(xb, batched=True)               # loop-batched default
        assert calls["n"] == 3
        for t in g.outputs:
            assert np.array_equal(ref[t], outb[t][0])
        # duplicate registration is an error unless overwrite=True
        with pytest.raises(BackendError):
            register_backend("test_custom", single=make_single)
        register_backend("test_custom", single=make_single, overwrite=True)
    finally:
        unregister_backend("test_custom")
    assert "test_custom" not in list_backends()


# -- save / load -------------------------------------------------------------

def test_save_load_round_trip(tmp_path):
    """Reloaded deployments reproduce identical outputs and WCET bound."""
    g, x = _graph_and_input()
    params = init_params(g, seed=7)
    dep = repro.compile(g, HW, backend="numpy", params=params,
                        use_cache=False)
    out0 = dep.run(x)
    path = str(tmp_path / "net.rtdep")
    assert dep.save(path) == path

    loaded = Deployment.load(path, machine=HW, graph=g)
    assert loaded.wcet_bound_s == dep.wcet_bound_s
    assert loaded.graph_signature == dep.graph_signature
    assert loaded.machine_fingerprint == dep.machine_fingerprint
    for backend in ("numpy", "jax", "pallas"):
        out = loaded.run(x, backend=backend)
        for t in g.outputs:
            assert np.array_equal(out0[t], out[t])
    # schedule + stage telemetry survive the round trip
    assert loaded.schedule.makespan == dep.schedule.makespan
    assert [s.name for s in loaded.stages] == [s.name for s in dep.stages]


def test_load_rejects_machine_mismatch(tmp_path):
    g, _ = _graph_and_input()
    dep = repro.compile(g, HW, use_cache=False)
    path = str(tmp_path / "net.rtdep")
    dep.save(path)
    other = dataclasses.replace(HW, scratchpad_bytes=HW.scratchpad_bytes * 2)
    with pytest.raises(ArtifactError, match="refusing to deploy"):
        Deployment.load(path, machine=other)
    # without a machine constraint the artifact still loads
    assert Deployment.load(path).wcet_bound_s == dep.wcet_bound_s


def test_load_rejects_graph_mismatch(tmp_path):
    g, _ = _graph_and_input()
    dep = repro.compile(g, HW, use_cache=False)
    path = str(tmp_path / "net.rtdep")
    dep.save(path)
    other = cnn.small_cnn(h=24, w=24)
    with pytest.raises(ArtifactError, match="refusing to deploy graph"):
        Deployment.load(path, graph=other)


def test_load_rejects_corrupt_artifacts(tmp_path):
    g, _ = _graph_and_input()
    dep = repro.compile(g, HW, use_cache=False)
    not_zip = tmp_path / "junk.rtdep"
    not_zip.write_bytes(b"not a deployment")
    with pytest.raises(ArtifactError):
        Deployment.load(str(not_zip))

    # a manifest whose signature disagrees with the embedded payload
    path = str(tmp_path / "net.rtdep")
    dep.save(path)
    tampered = str(tmp_path / "tampered.rtdep")
    with zipfile.ZipFile(path) as zin, \
            zipfile.ZipFile(tampered, "w") as zout:
        manifest = zin.read("manifest.json").replace(
            dep.graph_signature.encode(), b"deadbeefdeadbeef")
        zout.writestr("manifest.json", manifest)
        zout.writestr("payload.pkl", zin.read("payload.pkl"))
    with pytest.raises(ArtifactError, match="signature mismatch"):
        Deployment.load(tampered)

    # a corrupted payload fails the hash check BEFORE being unpickled
    corrupt = str(tmp_path / "corrupt.rtdep")
    with zipfile.ZipFile(path) as zin, \
            zipfile.ZipFile(corrupt, "w") as zout:
        zout.writestr("manifest.json", zin.read("manifest.json"))
        zout.writestr("payload.pkl", zin.read("payload.pkl")[:-10] + b"x" * 10)
    with pytest.raises(ArtifactError, match="payload hash mismatch"):
        Deployment.load(corrupt)

    # a structurally valid artifact missing payload keys stays ArtifactError
    import hashlib as _hashlib
    import pickle as _pickle
    import json as _json
    hollow = str(tmp_path / "hollow.rtdep")
    blob = _pickle.dumps({"schedule": None})
    with zipfile.ZipFile(path) as zin, \
            zipfile.ZipFile(hollow, "w") as zout:
        manifest = _json.loads(zin.read("manifest.json"))
        manifest["payload_sha256"] = _hashlib.sha256(blob).hexdigest()
        zout.writestr("manifest.json", _json.dumps(manifest))
        zout.writestr("payload.pkl", blob)
    with pytest.raises(ArtifactError):
        Deployment.load(hollow)


# -- taskset deployments -----------------------------------------------------

def test_compile_taskset_deployment():
    specs = [NetworkSpec("a", cnn.small_cnn(), 1 / 50),
             NetworkSpec("b", cnn.small_cnn(h=24, w=24), 1 / 100)]
    tdep = repro.compile(specs, HW, backend="numpy")
    assert isinstance(tdep, TasksetDeployment)
    assert tdep.schedulable
    assert set(tdep.deployments) == {"a", "b"}
    g = specs[0].graph
    x = np.random.default_rng(8).integers(
        -64, 64, (32, 32, 3)).astype(np.int8)
    params = tdep.deployments["a"].artifacts["quantize"]["params"]
    ref = reference_forward(g, params, {"input": x})
    out = tdep.run("a", x)
    for t in g.outputs:
        assert np.array_equal(ref[t], out[t])
    with pytest.raises(KeyError):
        tdep.run("nope", x)
    with pytest.raises(TypeError):                     # per-network deadlines
        repro.compile(specs, HW, deadline=1.0)


# -- serving integration -----------------------------------------------------

def test_multi_model_engine_attaches_deployments():
    """`Server.register` compiles each admitted CNN once into a cached
    Deployment (a second server admitting the same network gets the same
    artifact) and serves a submitted frame through it bit-exact, with
    deadline accounting."""
    from repro.serve import Server
    clear_deployment_cache()
    graphs = {"a": cnn.small_cnn(), "b": cnn.small_cnn(h=24, w=24)}
    params = {n: init_params(g, seed=i) for i, (n, g) in
              enumerate(graphs.items())}
    periods = {"a": 1 / 50, "b": 1 / 100}

    def server():
        srv = Server(HW, backend="numpy", num_cores=4, speed_ratio=1e12)
        for n, g in graphs.items():
            srv.register(n, g, period_s=periods[n], params=params[n])
        return srv

    srv = server()
    assert srv.report.schedulable
    executors = srv.executors
    assert set(executors) == {"a", "b"}
    for dep in executors.values():
        assert dep.backend == "numpy" and dep.wcet_bound_s > 0
    again = server().executors
    assert all(again[n] is executors[n] for n in graphs)
    frames = {n: np.random.default_rng(i).integers(
        -64, 64, size=g.tensors["input"].shape).astype(np.int8)
        for i, (n, g) in enumerate(graphs.items())}
    tickets = {n: srv.submit(n, x) for n, x in frames.items()}
    srv.run(hyperperiods=1)
    for n, g in graphs.items():
        ref = reference_forward(g, params[n], {"input": frames[n]})
        out = tickets[n].result().output
        for t in g.outputs:
            assert np.array_equal(ref[t], out[t])
    assert srv.monitor.checks == {"a": 1, "b": 1}
    assert srv.metrics["runner_calls"] == 2


def test_engine_exposes_deployment_and_loads_artifacts(tmp_path):
    """`Deployment.save` -> `Deployment.load` -> batched runner serves
    bit-exact."""
    g, x = _graph_and_input()
    params = init_params(g, seed=9)
    dep = repro.compile(g, HW, backend="numpy", params=params, num_cores=4)
    path = str(tmp_path / "net.rtdep")
    dep.save(path)

    loaded = Deployment.load(path, machine=HW)
    assert loaded.backend == "numpy"
    out = loaded.runner(batched=True)({"input": x[None]})
    ref = reference_forward(g, params, {"input": x})
    for t in g.outputs:
        assert np.array_equal(ref[t], out[t][0])
