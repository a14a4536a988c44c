"""Host seconds of ``Dataset.construct``'s bundling: finding the bundles on the
binning sample and building the bundled code matrix from the sparse rows (the
program's ``setup_seconds["find_bundles"]`` + ``["bundle_matrix"]``, spans
``dataset/construct/find_bundles`` and ``dataset/construct/bundle_matrix``).
None where the program times no such spans (the parent of the PR that added
them)."""

from chipbench import program_record


def read(facts):
    find = program_record.setup_seconds(facts, "find_bundles")
    build = program_record.setup_seconds(facts, "bundle_matrix")
    if find is None or build is None:
        return None
    return find + build
