#!/usr/bin/env python
"""Where tier-1's time goes, from the junit file of a whole run, and the
budget a PR's tests are held to (ROADMAP.md Queue 3 item 9).

    python scripts/tier1_seconds.py /tmp/_t1.xml [PARENT.xml]

Prints the sum of the cases' seconds, the twelve longest files and every
breach of the rule: a file over 400 s (under ``--dist loadfile`` a file
is one worker's), a file of 90 s or more without
``pytest.mark.long_file`` (it would start late), and, where the parent's
junit file of the same command on the same machine is given, more than
150 s added to the sum or a case over 60 s that the parent has not.
Exit status 1 on a breach.  The seconds are the whole run's, six
workers on shared cores: a file alone reads about 0.6 of them.
"""

import collections
import pathlib
import sys
import xml.etree.ElementTree as ET

CASE_S, FILE_S, MARKED_FROM_S, ADDED_S = 60, 400, 90, 150
REPO = pathlib.Path(__file__).resolve().parent.parent


def seconds_by_file(junit):
    files, cases = collections.Counter(), {}
    for case in ET.parse(junit).iter("testcase"):
        path = case.get("classname").replace(".", "/") + ".py"
        files[path] += float(case.get("time"))
        cases[f"{path}::{case.get('name')}"] = float(case.get("time"))
    return files, cases


def main(argv):
    files, cases = seconds_by_file(argv[1])
    total = sum(files.values())
    print(f"{len(cases)} cases, {total:.0f} s in all")
    for path, s in files.most_common(12):
        print(f"  {s:6.0f} s  {path}")
    breaches = [f"file over {FILE_S} s: {path} {s:.0f} s"
                for path, s in files.items() if s > FILE_S]
    breaches += [f"file of {s:.0f} s without pytest.mark.long_file: {path}"
                 for path, s in files.items() if s >= MARKED_FROM_S
                 and "mark.long_file(" not in (REPO / path).read_text()]
    known = set()
    if len(argv) > 2:
        before, known = seconds_by_file(argv[2])
        added = total - sum(before.values())
        print(f"parent {sum(before.values()):.0f} s: {added:+.0f} s")
        if added > ADDED_S:
            breaches.append(f"{added:.0f} s added, over {ADDED_S}")
    for name, s in cases.items():
        if s > CASE_S:
            print(f"  case over {CASE_S} s: {name} {s:.0f} s")
            if len(argv) > 2 and name not in known:
                breaches.append(f"new case over {CASE_S} s: {name}")
    for line in breaches:
        print("BREACH", line)
    return 1 if breaches else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
