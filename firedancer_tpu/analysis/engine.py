"""fdtlint driver: discovers the repo surface, runs every checker,
aggregates findings + coverage.

Two entry points:

  run_repo(root)    the full pass over /root/repo-shaped trees: ABI check
                    across tango/native x the binding modules, ring
                    discipline over tiles/ + disco/, purity over the
                    whole package.  This is what tier-1 asserts is clean.
  run_paths(paths)  targeted runs for fixtures and CLI arguments: .py
                    files get the AST checkers; directories containing C
                    sources get the ABI cross-check over their contents.
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path

from . import abi, procsafe, purity, ringlint, shmlint
from .findings import Finding

#: the ctypes binding modules the ABI checker must always cover — every
#: module that declares a signature table or calls into the native layer
#: on the hot path.  tests/test_fdtlint.py asserts coverage of this list,
#: so adding a binding module without extending it fails loudly.
BINDING_MODULES = [
    "firedancer_tpu/tango/rings.py",
    "firedancer_tpu/ops/ed25519/verify.py",
    "firedancer_tpu/ops/ed25519/sign.py",
    "firedancer_tpu/tiles/wire.py",
    "firedancer_tpu/tiles/bench.py",
    # call-site-only binders (no table, but fdt_* calls to arity-check)
    "firedancer_tpu/ballet/pack.py",
    "firedancer_tpu/ballet/zstd.py",
    "firedancer_tpu/tiles/pack.py",
    "firedancer_tpu/tiles/bank.py",
    "firedancer_tpu/flamenco/runtime.py",  # fdt_bank_* batch executor
    # block-egress natives (ISSUE 12): route-cache seeding + the
    # batched-datagram egress syscall
    "firedancer_tpu/tiles/net.py",
    "firedancer_tpu/tiles/quic.py",
]

#: directories the ring-discipline linter covers (the tile layer)
RING_DIRS = [
    "firedancer_tpu/tiles",
    "firedancer_tpu/disco",
    # the wire edge: QUIC + ingress admission policy (ISSUE 13) — the
    # hot-path-clock rule polices admission/shed classes here too
    "firedancer_tpu/waltz",
]


@dataclass
class Report:
    findings: list[Finding] = field(default_factory=list)
    coverage: dict = field(default_factory=dict)

    @property
    def ok(self) -> bool:
        return not self.findings

    def to_dict(self) -> dict:
        return {
            "ok": self.ok,
            "findings": [f.to_dict() for f in sorted(self.findings)],
            "coverage": self.coverage,
        }

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True)

    def render(self) -> str:
        if self.ok:
            cov = self.coverage
            return (
                "fdtlint: clean "
                f"({cov.get('abi', {}).get('call_sites', 0)} native call "
                f"sites, {len(cov.get('ring_files', []))} ring-lint files, "
                f"{cov.get('hot_functions', 0)} @hot_path functions, "
                f"{cov.get('shm_effects', 0)} shm effects in "
                f"{cov.get('shm_functions', 0)} native functions)"
            )
        return "\n".join(str(f) for f in sorted(self.findings))


def repo_root() -> Path:
    return Path(__file__).resolve().parent.parent.parent


def run_repo(root: Path | str | None = None) -> Report:
    root = Path(root) if root is not None else repo_root()
    rep = Report()

    # -- ABI: native sources x binding modules ---------------------------
    native = root / "firedancer_tpu" / "tango" / "native"
    c_paths = sorted(native.glob("*.h")) + sorted(native.glob("*.c"))
    py_paths = [root / m for m in BINDING_MODULES]
    missing = [str(p) for p in c_paths + py_paths if not p.exists()]
    if missing:
        raise FileNotFoundError(f"fdtlint repo surface missing: {missing}")
    abi_findings, abi_cov = abi.check(c_paths, py_paths, rel=root)
    rep.findings.extend(abi_findings)
    rep.coverage["abi"] = abi_cov

    # -- native C publish discipline (stem-emit-only, ISSUE 15) ----------
    # -- + C11 shared-memory effects contract (fdtshm, ISSUE 18) ---------
    native_c_files: list[str] = []
    shm_functions = 0
    shm_effects = 0
    for p in sorted(native.glob("*.c")):
        native_c_files.append(p.relative_to(root).as_posix())
        rep.findings.extend(ringlint.check_native_c_file(p, rel=root))
        rep.findings.extend(shmlint.check_native_c_file(p, rel=root))
        summ = shmlint.file_summary(p)
        shm_functions += summ["functions"]
        shm_effects += summ["effects"]
    rep.coverage["native_c_files"] = native_c_files
    # asserted coverage: a native file whose functions/effects the shm
    # analyzer cannot see would pass vacuously — counts make that loud
    rep.coverage["shm_functions"] = shm_functions
    rep.coverage["shm_effects"] = shm_effects

    # -- ring discipline + spawn safety: tiles/ + disco/ -----------------
    proc_safe_files = 0
    for d in RING_DIRS:
        for p in sorted((root / d).glob("*.py")):
            rep.findings.extend(procsafe.check_file(p, rel=root))
            proc_safe_files += 1
    rep.coverage["proc_safe_files"] = proc_safe_files
    ring_files: list[str] = []
    for d in RING_DIRS:
        for p in sorted((root / d).glob("*.py")):
            ring_files.append(p.relative_to(root).as_posix())
            rep.findings.extend(ringlint.check_file(p, rel=root))
    # tango/rings.py joins the scan for ring-mc-hook: every shared-memory
    # native op must route through the fdtmc scheduler hook, and the
    # guarded-function count is asserted coverage (a hook surface that
    # silently shrank would let ring ops hide from the model checker)
    rings_py = root / "firedancer_tpu" / "tango" / "rings.py"
    ring_files.append(rings_py.relative_to(root).as_posix())
    rings_findings, mc_hook_fns = ringlint.check_rings_file(rings_py, rel=root)
    rep.findings.extend(rings_findings)
    rep.coverage["ring_files"] = ring_files
    rep.coverage["mc_hook_fns"] = mc_hook_fns

    # -- purity: the whole package ---------------------------------------
    hot_fns = 0
    purity_files = 0
    for p in sorted((root / "firedancer_tpu").rglob("*.py")):
        if "analysis" in p.parts:
            continue  # the linter does not lint itself for hot-path purity
        f, n = purity.check_file(p, rel=root)
        rep.findings.extend(f)
        hot_fns += n
        purity_files += 1
    rep.coverage["hot_functions"] = hot_fns
    rep.coverage["purity_files"] = purity_files

    rep.findings.sort()
    return rep


def run_paths(paths: list[Path | str]) -> Report:
    """Targeted run for CLI args / lint-corpus fixtures.

    * .py file: ring + purity AST checkers.
    * directory: ABI cross-check over the directory's *.{c,h} x *.py
      (when it holds C sources), plus ring + purity over its *.py.
    """
    rep = Report()
    ring_files: list[str] = []
    hot_fns = 0
    for raw in paths:
        p = Path(raw)
        if p.is_dir():
            c_paths = sorted(p.glob("*.h")) + sorted(p.glob("*.c"))
            py_paths = sorted(p.rglob("*.py"))
            if c_paths:
                f, cov = abi.check(c_paths, py_paths, rel=p)
                rep.findings.extend(f)
                rep.coverage.setdefault("abi", cov)
                for cp in c_paths:
                    if cp.suffix == ".c":
                        rep.findings.extend(
                            ringlint.check_native_c_file(cp, rel=p)
                        )
                        rep.findings.extend(
                            shmlint.check_native_c_file(cp, rel=p)
                        )
            targets = py_paths
        elif p.suffix == ".c":
            # C fixture / targeted native-source run: publish discipline
            # (stem-emit-only) + the fdtshm shared-memory contract
            rep.findings.extend(ringlint.check_native_c_file(p))
            rep.findings.extend(shmlint.check_native_c_file(p))
            targets = []
        elif p.suffix == ".py":
            targets = [p]
        else:
            raise ValueError(f"fdtlint: cannot lint {p} (expected .py or dir)")
        for t in targets:
            ring_files.append(t.as_posix())
            rep.findings.extend(ringlint.check_file(t))
            rep.findings.extend(procsafe.check_file(t))
            f, n = purity.check_file(t)
            rep.findings.extend(f)
            hot_fns += n
    rep.coverage["ring_files"] = ring_files
    rep.coverage["hot_functions"] = hot_fns
    rep.findings.sort()
    return rep
