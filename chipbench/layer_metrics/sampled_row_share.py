"""Share of the rows in a window tree's bag, mean over the window's trees: the
program's own count (``TrainRecord`` ``sampled_rows``, from the device scalar
the sampler sums) over the configuration's rows.  None where the
configuration does not sample with GOSS, or the program keeps no such count
(the parent of the PR that added it)."""

from chipbench import program_record


def read(facts):
    if facts.config["params"].get("boosting") != "goss":
        return None
    rows = program_record.window_mean(facts, "sampled_rows")
    return None if rows is None else rows / facts.config["data"]["rows"]
