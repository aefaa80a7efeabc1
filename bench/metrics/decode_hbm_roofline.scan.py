"""decode_hbm_roofline.scan: the least time the chip's HBM needs to read
every compressed byte and write every decoded byte of the queries completed
in the window (bench.peaks.decode_bytes over the published bandwidth), as a
share of the time the device was busy with them: the window's length to
its last completion (host clock) times the busy share of the traced window
(device trace).  The traced window is the part of the window the profiler
recorded, so its busy share stands for the whole window.  Whatever
implements the decode, the bytes come from the cell's data.  A device
without published peaks is an error."""

from bench import peaks


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    done = [r for r in run.requests if r.done is not None and r.error is None]
    if not done:
        return None
    nbytes = sum(peaks.decode_bytes(run.system.compressed_bytes(r.item),
                                    run.system.decoded_bytes(r.item))
                 for r in done)
    least_s = nbytes / peaks.peaks(run.device_kind)["hbm_bytes_per_s"]
    busy_share = run.trace["busy_s"] / run.trace["window_s"]
    busy_s = busy_share * (max(r.done for r in done) - run.t0)
    return 100.0 * least_s / busy_s
