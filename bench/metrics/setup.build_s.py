"""Host seconds to make the cell's tables on the device, ended by
``block_until_ready``."""


def read(facts):
    return facts.get("build_s")
