"""Device: ``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, read
when the window has closed and before the reference runs."""


def read(facts: dict):
    return facts["peak_bytes"] or None
