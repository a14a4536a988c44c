"""Device columns over raw columns: the program's own ``TrainRecord``
``efb["bundles"] / efb["features"]`` as the data set was built (1 would be no
bundling; lower is better: every histogram pass streams that share of the
columns).  None where the program states no ``efb`` (the parent of the PR that
added it, or a data set that is not bundled)."""

from chipbench import program_record


def read(facts):
    efb = (program_record.snapshot(facts) or {}).get("efb") or {}
    if not efb.get("features"):
        return None
    return efb["bundles"] / efb["features"]
