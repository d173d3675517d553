"""The --digest of scripts/paged_rehearse.py WITH the Pallas bodies (base64 MLIR bytecode that
carries the call stacks' files, lines and columns), the checkout's path normalised inside them:
equal in two checkouts = no served program's compile-cache key moved."""
import base64, hashlib, re as _re, sys, types
root = sys.argv[1]
sys.path.insert(0, root)
sys.argv = ["paged_rehearse", "--digest"] + sys.argv[2:]
import scripts.paged_rehearse as pr
shim = types.SimpleNamespace(**{k: getattr(_re, k) for k in dir(_re) if not k.startswith("__")})
def body(m):
    raw = base64.b64decode(m.group(1)).replace(root.encode(), b"ROOT")
    return "BODY" + hashlib.sha256(raw).hexdigest()
def sub(pattern, repl, text, *a, **kw):
    if repl == "BODY":
        return _re.sub(r'\\22body\\22: \\22([^\\]*)\\22', body, text).replace(root, "ROOT")
    return _re.sub(pattern, repl, text, *a, **kw)
shim.sub = sub
pr.re = shim
sys.exit(pr.main())
