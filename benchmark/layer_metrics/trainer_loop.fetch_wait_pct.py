"""Share of the fit's wall time the step loop spent blocked on the input pipeline
(``train.fetch_wait`` spans of the program's recorder over the warm-up + window fit)."""


def read(ctx):
    obs = ctx.get("obs")
    if not obs or "train.fetch_wait" not in obs["phases"] or "train.step_dispatch" not in obs["phases"]:
        return None
    phases = obs["phases"]
    wall = sum(phases[name]["total_s"] for name in ("train.fetch_wait", "train.step_dispatch", "train.log_sync")
               if name in phases)
    return 100.0 * phases["train.fetch_wait"]["total_s"] / wall if wall > 0 else None
