"""Model operations of the train steps that ended in the traced window
(forward and backward, counted by the configuration's form in
bench/arch), over the window at the bf16 peak, in %."""
from bench import arch


def read(run):
    tr, t = run.get("trace"), run.get("train")
    if not tr or not t or not tr["modules"]:
        return None
    flops = len(tr["modules"]) * arch.load(run["mc"]).train_step_flops(
        run["mc"], t["batch"], t["text_len"])
    return 100.0 * flops / (tr["window_s"] * run["peak"]["bf16_flops"])
