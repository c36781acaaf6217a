from benchmarks.layer_util import delta


def read(run):
    """Dispatch rounds launched while another was still on the device
    (launched, not landed), of all rounds taken inside the window. None where
    the program's round counter does not say which were."""
    over = delta(run, "keto_dispatch_rounds_total", overlapped="true")
    total = over + delta(run, "keto_dispatch_rounds_total", overlapped="false")
    if total <= 0:
        return None
    return 100.0 * over / total
