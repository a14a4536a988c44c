"""Host seconds around ``Dataset.construct`` (bin finding and the bin matrix)."""


def read(facts):
    return facts.counters.get("binning_s")
