"""6 N T over (median step time x peak x chips), in %.  N is the
configuration as run; recomputation and attention's s^2 term are not
counted, so this is model FLOPs utilisation, not a roofline share."""
import statistics

from lib import counts


def read(rec):
    steps, peaks = rec.get("step_seconds"), rec.get("peaks")
    if not steps or not peaks:
        return None
    flops = counts.train_step_model_flops(rec["n_params"],
                                          rec["tokens_per_step"])
    return 100.0 * flops / (statistics.median(steps) * peaks["flops_per_s"]
                            * rec["chips"])
