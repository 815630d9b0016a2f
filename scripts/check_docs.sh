#!/usr/bin/env bash
# CI-style documentation lint. Fails (non-zero) on:
#   1. broken intra-repo links in docs/*.md or README.md;
#   2. public surfaces of src/repro/serve/aqp/ missing docstrings
#      (modules, public classes, public functions/methods);
#   3. a BuildParams / serving knob appearing in zero or in more than one
#      reference doc under docs/ (every knob must have exactly one home:
#      construction knobs in docs/construction.md, compression knobs in
#      docs/compression.md, serving knobs in docs/serving.md).
#
# Wired into scripts/tier1.sh and exercised by tests/test_docs.py, so the
# plain ROADMAP tier-1 command enforces it too.
set -euo pipefail
cd "$(dirname "$0")/.."
python - <<'EOF'
import ast
import pathlib
import re
import sys

ROOT = pathlib.Path(".").resolve()
errors = []

# ---------------------------------------------------------------- 1. links
LINK_RE = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
md_files = sorted(ROOT.glob("docs/*.md")) + [ROOT / "README.md"]
for md in md_files:
    if not md.exists():
        errors.append(f"missing documentation file: {md.relative_to(ROOT)}")
        continue
    for lineno, line in enumerate(md.read_text().splitlines(), 1):
        for target in LINK_RE.findall(line):
            if target.startswith(("http://", "https://", "mailto:", "#")):
                continue
            path = (md.parent / target.split("#", 1)[0]).resolve()
            if not path.exists():
                errors.append(f"{md.relative_to(ROOT)}:{lineno}: "
                              f"broken link -> {target}")

# ----------------------------------------------------- 2. serve/aqp docstrings
def check_docstrings(py: pathlib.Path):
    tree = ast.parse(py.read_text())
    rel = py.relative_to(ROOT)
    if ast.get_docstring(tree) is None:
        errors.append(f"{rel}: missing module docstring")
    def walk(node, prefix=""):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                name = child.name
                public = not name.startswith("_") or name == "__init__"
                if isinstance(child, ast.ClassDef):
                    if public and ast.get_docstring(child) is None:
                        errors.append(f"{rel}:{child.lineno}: class "
                                      f"{prefix}{name} missing docstring")
                    if public:      # a private class is not public surface
                        walk(child, prefix=f"{name}.")
                elif public and name != "__init__" \
                        and ast.get_docstring(child) is None:
                    errors.append(f"{rel}:{child.lineno}: def "
                                  f"{prefix}{name} missing docstring")

    walk(tree)

for py in sorted((ROOT / "src/repro/serve/aqp").glob("*.py")):
    check_docstrings(py)

# ------------------------------------------------------- 3. knob uniqueness
def class_fields(path, cls):
    for node in ast.parse((ROOT / path).read_text()).body:
        if isinstance(node, ast.ClassDef) and node.name == cls:
            return [st.target.id for st in node.body
                    if isinstance(st, ast.AnnAssign)
                    and isinstance(st.target, ast.Name)]
    raise SystemExit(f"cannot find {cls} in {path}")

# Cold-tier governor knobs (AQPServer kwargs) live with the cold-catalog
# docs in compression.md, not serving.md.
compression_knobs = ["from_compressed", "seed_from_bases",
                     "max_engine_bytes", "demote_idle_s"]
build_knobs = [k for k in class_fields("src/repro/core/types.py",
                                       "BuildParams")
               if k not in compression_knobs]
serving_knobs = ["mode", "plan_cache_size", "result_cache_size",
                 "max_result_bytes", "max_group", "min_group",
                 "max_wait_ms", "max_batch", "max_queue_depth",
                 "shed_policy", "retry_timeout_s",
                 "plan_templates", "template_cache_size", "planner_workers"]
obs_knobs = ["trace_enabled", "trace_buffer", "slow_query_ms"]
# Fault-tolerance knobs (per-query deadline + cold decode resilience)
# live with the robustness reference in robustness.md.
robustness_knobs = ["deadline_ms", "decode_retries", "decode_backoff_s",
                    "breaker_reset_s"]
docs = {p: p.read_text() for p in sorted(ROOT.glob("docs/*.md"))}
for knob, home in ([(k, "construction") for k in build_knobs]
                   + [(k, "compression") for k in compression_knobs]
                   + [(k, "serving") for k in serving_knobs]
                   + [(k, "observability") for k in obs_knobs]
                   + [(k, "robustness") for k in robustness_knobs]):
    pat = re.compile(rf"`{re.escape(knob)}`")
    hits = [p.name for p, text in docs.items() if pat.search(text)]
    if hits != [f"{home}.md"]:
        errors.append(f"knob `{knob}` must appear in exactly docs/{home}.md; "
                      f"found in {hits or 'no docs'}")

if errors:
    print("check_docs: FAIL", file=sys.stderr)
    for err in errors:
        print(f"  {err}", file=sys.stderr)
    sys.exit(1)
print(f"check_docs: OK ({len(md_files)} md files, "
      f"{len(build_knobs) + len(serving_knobs) + len(obs_knobs) + len(robustness_knobs)} knobs)")
EOF
