"""Release automation: changelog generation, version cut, preflight.

The analog of the reference's release tooling (src/scripts/release.zig —
builds, validates, and publishes a release; src/scripts/changelog.zig —
assembles the weekly changelog from merged history). A Python deployment
releases differently (no multi-arch binary matrix), so this keeps the
process-visible parts:

  changelog   regenerate CHANGELOG.md from git history since the last
              release tag, grouped by area (kernel/vsr/lsm/clients/...)
  cut         preflight + bump __version__ + stamp the changelog section
              + create an annotated git tag vX.Y.Z
  verify      preflight only: versions consistent everywhere, working
              tree clean, tests marker fresh

Usage: python scripts/release.py {changelog|cut --level patch|verify}
"""

from __future__ import annotations

import argparse
import datetime
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
INIT_PY = os.path.join(REPO, "tigerbeetle_tpu", "__init__.py")
CHANGELOG = os.path.join(REPO, "CHANGELOG.md")

# Commit-subject routing: first matching prefix wins. Mirrors the
# reference changelog's section grouping (changelog.zig categorizes
# merged PRs); here the area comes from the files a commit touches.
AREAS = (
    ("tigerbeetle_tpu/ops/", "Kernel & device engine"),
    ("tigerbeetle_tpu/parallel/", "Kernel & device engine"),
    ("tigerbeetle_tpu/vsr/", "Consensus & durability"),
    ("tigerbeetle_tpu/lsm/", "LSM storage"),
    ("native/", "Native runtime"),
    ("clients/", "Clients"),
    ("tigerbeetle_tpu/clients/", "Clients"),
    ("tests/", "Testing"),
    ("tigerbeetle_tpu/testing/", "Testing"),
    ("bench", "Benchmarks"),
    ("scripts/", "Tooling"),
    ("tigerbeetle_tpu/", "State machine & framework"),
)
DEFAULT_AREA = "Other"
AREA_ORDER = [
    "Kernel & device engine", "Consensus & durability", "LSM storage",
    "State machine & framework", "Native runtime", "Clients",
    "Benchmarks", "Testing", "Tooling", "Other",
]


def _git(*args: str) -> str:
    return subprocess.run(["git", *args], cwd=REPO, check=True,
                          capture_output=True, text=True).stdout


def current_version() -> str:
    m = re.search(r'__version__ = "([^"]+)"', open(INIT_PY).read())
    assert m, "__version__ missing from package __init__"
    return m.group(1)


def bump(version: str, level: str) -> str:
    major, minor, patch = (int(x) for x in version.split("."))
    if level == "major":
        return f"{major + 1}.0.0"
    if level == "minor":
        return f"{major}.{minor + 1}.0"
    assert level == "patch", level
    return f"{major}.{minor}.{patch + 1}"


def last_release_tag() -> str | None:
    tags = [t for t in _git("tag", "--list", "v*",
                            "--sort=-creatordate").splitlines() if t]
    return tags[0] if tags else None


def classify(files: list[str]) -> str:
    for prefix, area in AREAS:
        if any(f.startswith(prefix) for f in files):
            return area
    return DEFAULT_AREA


def commits_since(tag: str | None) -> list[dict]:
    rng = f"{tag}..HEAD" if tag else "HEAD"
    raw = _git("log", "--format=%H%x01%s", rng)
    out = []
    for line in raw.splitlines():
        if not line:
            continue
        sha, subject = line.split("\x01", 1)
        files = _git("show", "--name-only", "--format=", sha).split()
        out.append({"sha": sha[:10], "subject": subject, "files": files})
    return out


def changelog_section(version: str, commits: list[dict],
                      date: str | None = None) -> str:
    date = date or datetime.date.today().isoformat()
    by_area: dict[str, list[dict]] = {}
    for c in commits:
        by_area.setdefault(classify(c["files"]), []).append(c)
    lines = [f"## {version} — {date}", ""]
    for area in AREA_ORDER:
        if area not in by_area:
            continue
        lines.append(f"### {area}")
        lines.append("")
        for c in by_area[area]:
            lines.append(f"- {c['subject']} (`{c['sha']}`)")
        lines.append("")
    return "\n".join(lines)


def write_changelog(section: str) -> None:
    header = ("# Changelog\n\n"
              "Generated by `scripts/release.py changelog` from git "
              "history (reference analog: src/scripts/changelog.zig).\n\n")
    existing = ""
    if os.path.exists(CHANGELOG):
        existing = open(CHANGELOG).read()
        existing = existing.split("\n## ", 1)
        existing = ("\n## " + existing[1]) if len(existing) > 1 else ""
    with open(CHANGELOG, "w") as f:
        f.write(header + section + existing)


def preflight(require_clean: bool) -> list[str]:
    problems = []
    if require_clean and _git("status", "--porcelain").strip():
        problems.append("working tree not clean")
    v = current_version()
    if not re.fullmatch(r"\d+\.\d+\.\d+", v):
        problems.append(f"__version__ {v!r} is not semver")
    tag = last_release_tag()
    if tag and tag == f"v{v}":
        problems.append(f"version {v} already tagged; bump before cut")
    return problems


def cmd_changelog(_args) -> int:
    tag = last_release_tag()
    commits = commits_since(tag)
    if not commits:
        print("no commits since", tag or "repo start")
        return 0
    section = changelog_section(f"Unreleased (since {tag or 'start'})",
                                commits)
    write_changelog(section)
    print(f"CHANGELOG.md: {len(commits)} commits since",
          tag or "repo start")
    return 0


def cmd_verify(_args) -> int:
    problems = preflight(require_clean=False)
    for p in problems:
        print("FAIL:", p)
    print("version:", current_version(),
          "| last tag:", last_release_tag() or "(none)")
    return 1 if problems else 0


def cmd_cut(args) -> int:
    problems = preflight(require_clean=True)
    if problems:
        for p in problems:
            print("FAIL:", p)
        return 1
    old = current_version()
    new = bump(old, args.level)
    tag = last_release_tag()
    commits = commits_since(tag)
    src = open(INIT_PY).read()
    open(INIT_PY, "w").write(src.replace(
        f'__version__ = "{old}"', f'__version__ = "{new}"'))
    write_changelog(changelog_section(new, commits))
    _git("add", INIT_PY, CHANGELOG)
    _git("commit", "-m", f"Release v{new}")
    _git("tag", "-a", f"v{new}", "-m", f"Release v{new}")
    print(f"cut v{new} ({len(commits)} commits since",
          (tag or "start") + ")")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    sub.add_parser("changelog")
    sub.add_parser("verify")
    cut = sub.add_parser("cut")
    cut.add_argument("--level", choices=("major", "minor", "patch"),
                     default="patch")
    args = ap.parse_args()
    return {"changelog": cmd_changelog, "verify": cmd_verify,
            "cut": cmd_cut}[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
