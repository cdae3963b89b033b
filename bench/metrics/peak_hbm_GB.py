"""Peak device memory of the run (``memory_stats()['peak_bytes_in_use']`` on
the fullest chip), in GB: it sets the cohort one chip holds."""


def read(run):
    return run.memory_peak_bytes / 1e9
