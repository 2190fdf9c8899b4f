"""Peak device memory of the fullest chip, in GiB: the larger of the
runtime's ``peak_bytes_in_use``, read once the window has closed (before
the reference runs), and what the compiled programs of the window need by
their own memory analysis (the runtime's peak has been seen to leave out
a program's temporaries; PERF.md)."""


def read(rec):
    return max(rec["memory_peak_bytes"],
               rec.get("memory_compiled_bytes", 0)) / 2 ** 30
