"""peak_hbm_gb: the device allocator's ``peak_bytes_in_use`` after the
window, in GB.  It covers the build's staging peak as well: a deployment
has to fit both."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 1e9
