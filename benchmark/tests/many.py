"""Runs several ``benchmark/run.py`` calls one after another (each its own
process: a chip belongs to one process at a time) and gathers their result
lines. For the builder's sessions on the chip; the driver's check never calls
it. It does not touch JAX.

    python benchmark/tests/many.py <out.jsonl> -- <run.py arguments> [-- <run.py arguments> ...]

Each group of arguments is one run. Prints, per run, its seconds, exit code,
metrics and compared numbers; appends the full result lines to ``out.jsonl``.
"""
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv):
    out_path, rest = argv[0], argv[1:]
    max_setup = None
    if rest and rest[0] == "--max-setup":      # stop if a warm run sets up slower
        max_setup, rest = float(rest[1]), rest[2:]
    print("JAX env: %s" % {k: v for k, v in os.environ.items() if k.startswith("JAX")}, flush=True)
    groups, cur = [], None
    for a in rest:
        if a == "--":
            cur = []
            groups.append(cur)
        else:
            cur.append(a)
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    for g in groups:
        t0 = time.time()
        p = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py")] + g,
                           cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        dt = time.time() - t0
        last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
        try:
            line = json.loads(last)
        except ValueError:
            line = None
        print("=== %s -> exit %d in %.1f s" % (" ".join(g), p.returncode, dt), flush=True)
        if line is None:
            print(p.stderr[-3000:])
            print(p.stdout[-1000:])
            continue
        print("  correct %s attempted %s failed %s peak %.2f GB" % (
            line["correct"], line["attempted"], line["failed"],
            line["device"].get("memory_peak_bytes", 0) / 1e9))
        print("  metrics " + " ".join("%s=%.6g" % (k, v["value"]) for k, v in line["metrics"].items()))
        print("  compared " + " ".join("%s=%.4g/%.4g" % (k, v["value"], v["limit"])
                                       for k, v in line["compared"].items()))
        if "busy_s" in line["device"]:
            print("  busy %.4f of %.4f s" % (line["device"]["busy_s"], line["device"]["window_s"]))
        if "breakdown" in line:
            print("  ops  " + " ".join("%s:%.4f" % (n[:28], s) for n, s in line["breakdown"]["device_ops"]))
            print("  gaps " + " ".join("%s:%.4f" % (n, s) for n, s in line["breakdown"]["idle_gaps"]))
        for l in p.stderr.splitlines():
            if l.startswith(("grad1", "change3:", "benchmark:")):
                print("  " + l)
            elif "Finished XLA compilation" in l or "ersistent" in l:
                if "jit(step)" in l or "jit_step" in l or "_grad_step" in l \
                        or "gen" in l or "pure" in l or "rror" in l \
                        or "exceeds" in l:
                    print("  " + l.split(": ", 1)[-1][:200])
        cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
            os.path.join(ROOT, ".jax_cache")
        if os.path.isdir(cache):
            files = [os.path.join(cache, f) for f in os.listdir(cache)]
            print("  compile cache %s: %d files, %.1f MB" % (
                cache, len(files), sum(os.path.getsize(f) for f in files if os.path.isfile(f)) / 1e6))
        with open(out_path, "a") as f:
            f.write(json.dumps({"args": g, "seconds": dt, "exit": p.returncode, "line": line}) + "\n")
        setup = line["metrics"].get("setup_s", {}).get("value")
        if max_setup is not None and g is not groups[0] and setup is not None and setup > max_setup:
            print("!!! set-up took %.0f s on a warm run: stopping here" % setup, flush=True)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
