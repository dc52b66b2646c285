"""Host time per served chunk: the benchmark's span around each
``serve_columnar`` call minus the device-busy time inside it (dense block
build, host copies, IO accounting), in milliseconds."""


def read(run):
    if run.trace is None or not run.trace.chunks or not run.trace.devices:
        return None
    per = run.trace.host_only_s()
    return 1e3 * sum(per) / len(per)
