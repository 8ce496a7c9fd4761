"""The comparison that decides ``correct``: what the timed path produced
in its first steps against the reference's, number by number, each with a
limit of its own (``limits/<workload>.json``; PERF.md says what each was
set from)."""
import math
import statistics

DEAD = 0.1    # a leaf whose norm is under this share of the reference's


def leaf_gaps(got, want):
    """By leaf, the gap between two norms (the gap between the norms, not
    the norm of a difference), measured against the reference's norm of
    that leaf or of the median leaf, whichever is larger: some gradients
    are all but zero."""
    floor = statistics.median(want.values())
    return {k: abs(got[k] - want[k]) / max(want[k], floor) for k in want}


def whole_gap(got, want):
    """The gap between the norms over all leaves together."""
    a = math.sqrt(sum(got[k] ** 2 for k in want))
    b = math.sqrt(sum(v ** 2 for v in want.values()))
    return abs(a - b) / b


def dead_leaves(got, want):
    """The leaves whose norm is under ``DEAD`` of the reference's: a
    parameter the step gave no gradient, or never moved."""
    floor = statistics.median(want.values())
    return sorted(k for k in want
                  if want[k] >= floor * DEAD and got[k] < DEAD * want[k])


def logprob_rms_gap(got, want):
    """rms over rows x classes of the gap between two log-probabilities."""
    d = got - want
    return float(math.sqrt((d * d).mean()))


def excess_noise(got, floor, want):
    """How much more rounding noise ``got`` carries than ``floor``, both
    read against ``want``, in units of ``floor``'s noise power:
    (rms(got - want)^2 - rms(floor - want)^2) / rms(floor - want)^2.
    ``floor`` is the plain reference at the precision the configuration
    states; a float32 program reads -1, one as noisy as ``floor`` 0."""
    g, f = logprob_rms_gap(got, want), logprob_rms_gap(floor, want)
    return (g * g - f * f) / (f * f), g, f


def compare(got, want, limits):
    """``got``: {"losses": [...], "grad_norms": {leaf: n}, "delta_norms":
    {leaf: n}, "logprob": rows x classes}; ``want`` the same from the
    float32 reference, with "logprob_stated", the reference's
    log-probabilities at the stated precision. Returns rows (name, value,
    limit, note); ``correct`` is every value <= its limit."""
    rows = []
    for i, (a, b) in enumerate(zip(got["losses"], want["losses"])):
        name = "loss_step%d_rel_gap" % (i + 1)
        rows.append((name, abs(a - b) / abs(b), limits[name],
                     "%.6f vs %.6f" % (a, b)))
    # PRECISION. The log-probabilities the step put out at the seeded
    # weights, all rows and classes: one forward pass, no chaos of
    # training between the rounding and the reading, a quarter of a
    # million samples. Their rms gap to float32 is rounding noise, most
    # of which ANY pipeline that stores its tensors at the stated
    # precision carries; the reference at that precision measures that
    # floor on the same weights and rows, and what the program carries
    # beyond it is the number: a step down in precision multiplies it
    ex, g, f = excess_noise(got["logprob"], want["logprob_stated"],
                            want["logprob"])
    rows.append(("step1_excess_noise", ex, limits["step1_excess_noise"],
                 "rms gap to float32 %.5f, the stated precision's own %.5f,"
                 " %d rows x %d classes" % ((g, f) + want["logprob"].shape)))
    # THE UPDATE. All leaves together, then by the median leaf: sound
    # runs' single leaves swing by a third (early BatchNorm leaves, the
    # transient from seeded weights), their median by a hundredth, and a
    # wrong learning rate, momentum or gradient scale moves every leaf
    rows.append(("grad_norm_gap",
                 whole_gap(got["grad_norms"], want["grad_norms"]),
                 limits["grad_norm_gap"], "all leaves together"))
    dead = []
    for key in ("grad_norms", "delta_norms"):
        gaps = leaf_gaps(got[key], want[key])
        worst = max(gaps, key=gaps.get)
        name = key[:-1] + "_median_leaf_gap"
        rows.append((name, statistics.median(gaps.values()), limits[name],
                     "worst leaf %.4f %s" % (gaps[worst], worst)))
        dead += dead_leaves(got[key], want[key])
    rows.append(("dead_leaves", float(len(dead)), limits["dead_leaves"],
                 " ".join(dead[:4]) or "none"))
    return rows
