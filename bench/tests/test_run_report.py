"""The run's report line on the host's memory."""

import os
import re

import run


def _rss_now():
    with open("/proc/self/status") as fh:
        for line in fh:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) * 1024
    raise AssertionError("no VmRSS in /proc/self/status")


def test_host_memory_line_gives_the_peak_and_the_cpu_count():
    line = run.host_memory_line("warm-up")
    m = re.fullmatch(r"host memory: peak_rss_bytes (\d+) after warm-up "
                     r"\(cpu_count (\d+)\)", line)
    assert m, line
    assert int(m.group(2)) == os.cpu_count()
    # bytes, not KiB: about what the process holds now or more (the
    # kernel's two counters differ by a few pages)
    assert int(m.group(1)) >= _rss_now() // 2
