"""Model FLOPs per decision (trunk convs, activation basis, per-sample
mixing at the served mean sample count) times the traced run's
decisions/s, over chips times the bf16 peak."""

from bench.readout import head_flops, trunk_flops


def read(run):
    if not run.decisions or run.peaks is None:
        return None
    m = run.cfg["model"]
    window = [r for r in run.records.values()
              if run.t0 <= r.verdict_s <= run.t1]
    samples = sum(r.n_samples for r in window) / len(window)
    per_decision = (trunk_flops(m["image_size"], m["channels"], m["kernel"])
                    + head_flops(m["channels"][-1], m["n_classes"])
                    + samples * 2 * 16 * m["n_classes"])
    rate = run.decisions / run.window_s
    return 100.0 * per_decision * rate / (run.chips
                                          * run.peaks["bf16_flops"])
