"""The program's ``shisha.*`` spans and scopes, on the profiler's clock.

``PipelineRunner``'s program carries its steps' named scopes in its op-name
metadata, on one host device and on four, and still computes what the
sequential model does; with the scopes left out it compiles to the same
program once metadata is stripped; a profiled ``run`` is one ``shisha.run``
span per call; a profiled ``MeasuringEvaluator`` build writes, inside
``shisha.measure``, a warm and a timed span for each layer it compiles, and
only the timed span for each layer whose program an earlier build compiled.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core import PipelineConfig, paper_platform
from repro.launch.mesh import make_stage_mesh
from repro.models.cnn import PATCHES_SCOPE, canonical_pipeline_apply, make_cnn, network_layers
from repro.pipeline import MeasuringEvaluator, PipelineRunner

REPO = Path(__file__).resolve().parents[1]
RUNNER_SCOPES = ("shisha.init", "shisha.inject", "shisha.emit", "shisha.ship", "shisha.gather", "shisha.canon")


@pytest.mark.parametrize("devices", [1, 4])
def test_runner_program_carries_its_scopes(devices):
    code = f"""
        import re
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import generate_seed, paper_platform
        from repro.models.cnn import make_cnn, network_layers, canonical_pipeline_apply
        from repro.launch.mesh import make_stage_mesh
        from repro.pipeline import PipelineRunner

        assert len(jax.devices()) == {devices}
        model = make_cnn("synthnet", scale=0.1)
        params = model.init(jax.random.PRNGKey(0))
        seed = generate_seed([l.weight for l in network_layers("synthnet")], paper_platform(4), n_stages={devices})
        in_shape = (8, 8, 8)
        apply_fn, to_canon, crop_out, _ = canonical_pipeline_apply(model, params, in_shape)
        runner = PipelineRunner(mesh=make_stage_mesh({devices}), conf=seed.conf, apply_layer=apply_fn, n_micro=3)
        raw = jax.random.normal(jax.random.PRNGKey(1), (3, 2) + in_shape)
        micro = jax.vmap(to_canon)(raw)
        text = runner._run.lower(micro).compile().as_text()
        print(sorted(set(re.findall(r'op_name="[^"]*?(shisha\\.\\w+)', text))))
        out = crop_out(runner.run(micro))
        ref = jnp.stack([model(params, raw[i]) for i in range(3)])
        assert np.allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4), "pipeline != sequential"
        """
    # synthnet's first layer (3 input channels) builds its patch matrix too
    assert on_host_devices(devices, code) == str(sorted(RUNNER_SCOPES + (PATCHES_SCOPE,)))


@pytest.mark.parametrize("devices", [1, 4])
def test_scopes_change_only_metadata(devices):
    code = f"""
        import contextlib, re
        import jax, jax.numpy as jnp
        from repro.core import generate_seed, paper_platform
        from repro.models.cnn import make_cnn, network_layers, canonical_pipeline_apply
        from repro.launch.mesh import make_stage_mesh
        from repro.pipeline import PipelineRunner

        model = make_cnn("synthnet", scale=0.1)
        params = model.init(jax.random.PRNGKey(0))
        seed = generate_seed([l.weight for l in network_layers("synthnet")], paper_platform(4), n_stages={devices})

        def compiled():
            apply_fn, to_canon, _, _ = canonical_pipeline_apply(model, params, (8, 8, 8))
            runner = PipelineRunner(mesh=make_stage_mesh({devices}), conf=seed.conf, apply_layer=apply_fn, n_micro=3)
            return runner._run.lower(jax.vmap(to_canon)(jnp.ones((3, 2, 8, 8, 8)))).compile().as_text()

        def stripped(text):
            # drop the source-location tables and every op's metadata
            tables = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
            blocks = [b for b in text.split("\\n\\n") if b.split("\\n", 1)[0] not in tables]
            return re.sub(r",? metadata=\\{{[^}}]*\\}}", "", "\\n\\n".join(blocks))

        scoped = compiled()
        named_scope = jax.named_scope
        jax.named_scope = lambda name: contextlib.nullcontext() if name.startswith("shisha.") else named_scope(name)
        plain = compiled()
        assert "shisha." in scoped and "shisha." not in plain
        print(stripped(scoped) == stripped(plain))
        """
    assert on_host_devices(devices, code) == "True"


def on_host_devices(devices: int, code: str) -> str:
    """Runs ``code`` on ``devices`` host CPU devices; its last line of output."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(REPO / "src")}
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)], capture_output=True, text=True, timeout=300, env=env, cwd=REPO
    )
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout.strip().splitlines()[-1]


def host_spans(trace_dir: Path) -> list:
    """(name, start_ns, end_ns, metadata) of the ``shisha.*`` host spans of a trace."""
    from jax.profiler import ProfileData

    (path,) = trace_dir.glob("**/*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("shisha."):
                    spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def test_runner_run_is_one_span_per_call(tmp_path):
    model = make_cnn("synthnet", scale=0.1)
    params = model.init(jax.random.PRNGKey(0))
    apply_fn, to_canon, _, _ = canonical_pipeline_apply(model, params, (8, 8, 8))
    runner = PipelineRunner(
        mesh=make_stage_mesh(1), conf=PipelineConfig(stages=(len(model.specs),), eps=(0,)), apply_layer=apply_fn, n_micro=2
    )
    micro = jax.vmap(to_canon)(jnp.ones((2, 1, 8, 8, 8)))
    jax.block_until_ready(runner.run(micro))
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        jax.block_until_ready(runner.run(micro))
    jax.profiler.stop_trace()
    spans = host_spans(tmp_path)
    assert [(name, meta) for name, _, _, meta in spans] == [("shisha.run", {})] * 3
    assert all(end <= start for (_, _, end, _), (_, start, _, _) in zip(spans, spans[1:]))


def test_measuring_evaluator_spans(tmp_path):
    model = make_cnn("synthnet", scale=0.1)
    params = model.init(jax.random.PRNGKey(0))
    n = 3
    first = model.specs[0]
    hw = first.h_out * first.stride
    xs, x = [], jnp.ones((1, hw, hw, first.c_in))
    for i in range(n):
        xs.append(x)
        x = model.apply_layer(i, params[i], x)
    fns = [lambda p, x, i=i: model.apply_layer(i, p, x) for i in range(n)]
    layers = network_layers("synthnet")[:n]

    def build():
        return MeasuringEvaluator(paper_platform(2), layers, layer_fns=fns, layer_args=list(zip(params, xs)), reps=2)

    jax.profiler.start_trace(str(tmp_path))
    evs = [build(), build()]  # the first compiles, the second reuses
    jax.profiler.stop_trace()
    for ev in evs:
        assert len(ev._measured) == n and all(t > 0 for t in ev._measured)
    spans = host_spans(tmp_path)
    measures = [s for s in spans if s[0] == "shisha.measure"]
    assert len(measures) == 2
    want = [[], []]
    for i in range(n):
        want[0] += [("shisha.measure.warm", {"layer": i}), ("shisha.measure.timed", {"layer": i, "calls": 2, "cached": 0})]
        want[1] += [("shisha.measure.timed", {"layer": i, "calls": 2, "cached": 1})]
    for measure, want_inner in zip(measures, want):
        inner = [(name, meta) for name, a, b, meta in spans if name != "shisha.measure" and measure[1] <= a <= b <= measure[2]]
        assert inner == want_inner
    assert sum(name != "shisha.measure" for name, _, _, _ in spans) == 3 * n
