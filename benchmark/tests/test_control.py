"""``correct`` has to be able to come out false. At the cells' toy sizes (float32 on the
CPU, where a sound run agrees with the reference to rounding):

* the control (the reference in a lower precision, in the program's place) fails;
* a run whose timed path is broken underneath (a train step that returns its state
  unchanged, or trains on half of each batch; a served token altered where the engine
  hands it out) fails,

with the harness's look for a chip skipped and the rest of a run driven as it is."""

import pytest

from benchmark.harness import device, manifest
from benchmark.harness.loops import driver_for


def _drive(cell_name: str, tmp_path, precision: str = "float32", seconds: float = 1.5, seed: int = 2**31 + 3,
           engine: dict | None = None):
    cell = manifest.rehearsal_cell(manifest.resolve_cell(cell_name))
    cell["settings"].get("engine", {}).update(engine or {})
    device.enable_caches()
    env = {"seed": seed, "seconds": seconds, "trace": False, "rehearse": True,
           "monitor": device.CompileMonitor(), "scratch": str(tmp_path), "device": {},
           "memory_peak_bytes": lambda: 0, "window_opened": lambda t: None,
           "reference_precision": precision}
    return driver_for(cell["traffic"]["kind"]).run(cell, env)


def _by_name(outcome) -> dict:
    return {row["check"]: row for row in outcome["checks"].rows}


CELLS = {w["name"]: manifest.load_traffic(w["traffic"])["kind"] for w in manifest.load_manifest()["workloads"]}
TRAIN = [c for c, kind in CELLS.items() if kind == "train"]
SERVE = [c for c, kind in CELLS.items() if kind != "train"]


@pytest.mark.parametrize("cell", TRAIN + SERVE)
def test_sound_run_is_correct_and_control_is_not(cell, tmp_path):
    sound = _drive(cell, tmp_path)
    assert sound["checks"].ok, sound["checks"].rows
    control = _drive(cell, tmp_path, precision="float8")
    assert not control["checks"].ok, control["checks"].rows


@pytest.mark.parametrize("cell", SERVE)
def test_the_programs_own_int8_path_is_not_correct(cell, tmp_path):
    outcome = _drive(cell, tmp_path, engine={"weight_dtype": "int8", "kv_quant": "int8"})
    assert not outcome["checks"].ok, outcome["checks"].rows


@pytest.mark.parametrize("cell", TRAIN)
def test_a_step_that_returns_its_state_unchanged_is_caught(cell, tmp_path, monkeypatch):
    from perceiver_io_tpu.training import trainer

    real = trainer.make_causal_lm_train_step

    def broken(model, tx, max_latents, **kwargs):
        step = real(model, tx, max_latents, **kwargs)

        def unchanged(state, batch):
            _, metrics = step(state, batch)
            return state.replace(step=state.step + 1), metrics

        return unchanged

    monkeypatch.setattr(trainer, "make_causal_lm_train_step", broken)
    rows = _by_name(_drive(cell, tmp_path))
    assert not rows["update_norm_gap_worst_leaf"]["ok"] and not rows["first_gradient_norm_gap_worst_leaf"]["ok"]
    assert rows["loss_gap_first_steps"]["value"] > 0


def half_batch_step(real):
    """``make_causal_lm_train_step`` whose step leaves out the second half of every
    batch: the first half stands in its place, so shapes and programs stay the same."""

    def broken(model, tx, max_latents, **kwargs):
        step = real(model, tx, max_latents, **kwargs)

        def half(state, batch):
            import jax
            import jax.numpy as jnp

            keep = lambda x: jnp.concatenate([x[: x.shape[0] // 2]] * 2)
            return step(state, jax.tree.map(keep, batch))

        return half

    return broken


@pytest.mark.parametrize("cell", TRAIN)
def test_a_part_of_the_batch_left_out_is_caught(cell, tmp_path, monkeypatch):
    from perceiver_io_tpu.training import trainer

    monkeypatch.setattr(trainer, "make_causal_lm_train_step", half_batch_step(trainer.make_causal_lm_train_step))
    rows = _by_name(_drive(cell, tmp_path))
    # at the real size the gradient and the update catch it (5 x and 2 x their limits);
    # the loss gap read 0.015 against 0.02 there (PERF.md section 6, PR 23)
    assert not rows["first_gradient_norm_gap_worst_leaf"]["ok"] and not rows["update_norm_gap_worst_leaf"]["ok"]


@pytest.mark.parametrize("cell", SERVE)
def test_an_altered_token_is_caught(cell, tmp_path, monkeypatch):
    from perceiver_io_tpu.serving.engine import ServingEngine

    real = ServingEngine.step_harvest

    def altered(self):
        more = real(self)
        for request in list(self._requests.values()):
            if len(request.output_ids) == 3 and not getattr(request, "_bench_altered", False):
                request.output_ids[-1] = (request.output_ids[-1] + 1) % self.model.config.vocab_size
                request._bench_altered = True
        return more

    monkeypatch.setattr(ServingEngine, "step_harvest", altered)
    outcome = _drive(cell, tmp_path)
    assert not outcome["checks"].ok
    assert not _by_name(outcome)["served_token_deficit_max"]["ok"]
