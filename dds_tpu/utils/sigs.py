"""Record keys, nonces and HMAC signatures.

Counterpart of the reference's `utils/Utils.scala:15-57`: SHA-512 content
hashes for record keys, SecureRandom nonces, and two HMAC families — the
intranet (replica<->replica) "ABD" signature over (value, tag, nonce) and
the proxy<->replica signature over (key[, value], nonce). All comparisons
are constant-time.

Deviations (flagged per SURVEY.md §7):
- The reference's ABD signature covers `tag.seq + 1` instead of `tag.seq`
  (`Utils.scala:33`) — harmless but weird; we sign the actual seq.
- Values are serialized as canonical JSON, not JVM `toString`.
"""

from __future__ import annotations

import hashlib
import hmac
import json
import secrets


def canonical(value) -> str:
    """Deterministic serialization of a JSON-ish value for hashing/signing."""
    return json.dumps(value, sort_keys=True, separators=(",", ":"), default=str)


def key_from_set(contents: list) -> str:
    """SHA-512 content-hash record key (hex, upper) — `Utils.scala:15-18`."""
    return hashlib.sha512(canonical(contents).encode()).hexdigest().upper()


def random_key() -> str:
    """Random SHA-512 record key — `Utils.scala:21-26`."""
    return hashlib.sha512(secrets.token_bytes(100)).hexdigest().upper()


def generate_nonce() -> int:
    return secrets.randbits(63)


def _mac(secret: bytes, content: bytes) -> bytes:
    return hmac.new(secret, content, hashlib.sha256).digest()


def abd_signature(secret: bytes, value, tag, nonce: int) -> bytes:
    """Intranet replica signature over (value, tag, nonce)."""
    content = f"{canonical(value)}|{tag.seq}|{tag.id}|{nonce}".encode()
    return _mac(secret, content)


def validate_abd_signature(secret: bytes, value, tag, nonce: int, given: bytes) -> bool:
    return hmac.compare_digest(abd_signature(secret, value, tag, nonce), given)


def tag_payload(tag):
    """Canonical JSON-safe form of one tag for signing: [seq, id] (None
    stays None). Tags are predictable (seq, coordinator-id), so reply
    MACs must cover them — otherwise an in-transit attacker could swap a
    guessed future tag and later turn the proxy's tag-validated cache
    into a stale serve."""
    return None if tag is None else [tag.seq, tag.id]


def tags_blob(tags) -> bytes:
    """Packed byte form of a tag vector for MACs and fingerprints:
    "seq:len(id):id" fields joined by ";". Both the replica (signer) and
    proxy (verifier) derive this from their own ABDTag objects so
    wire-codec differences can't skew the MAC input. The id is length-
    prefixed because ids originate from wire messages and are never
    charset-checked — without the prefix, delimiter characters inside an
    id would make the packing non-injective and two distinct vectors
    could share one MAC. ~6x cheaper than canonical JSON at K=8192,
    which matters where it still runs once per aggregate: the proxy's
    verify of each full reply. The replica's reply and the proxy's request
    are made from kept `tag_field`s (`fields_blob`) and format no tag."""
    return ";".join(f"{t.seq}:{len(t.id)}:{t.id}" for t in tags).encode()


def tag_field(tag) -> str:
    """One tag's field of `tags_blob`: what a caller keeps per tag of a
    vector it patches in place, so that `fields_fingerprint` costs one
    join and one hash and no formatting."""
    return f"{tag.seq}:{len(tag.id)}:{tag.id}"


def fields_blob(fields) -> bytes:
    """`tags_blob` of the vector whose `tag_field`s these are: one join."""
    return ";".join(fields).encode()


def blob_fingerprint(blob: bytes) -> bytes:
    """`tags_fingerprint` of the vector whose `tags_blob` this is."""
    return hashlib.sha256(blob).digest()


def fields_fingerprint(fields) -> bytes:
    """`tags_fingerprint` of the vector whose `tag_field`s these are."""
    return blob_fingerprint(fields_blob(fields))


def tags_fingerprint(tags) -> bytes:
    """Order-sensitive digest of a tag vector. Equal fingerprints (within
    one key-set request order) mean equal per-key tags — the whole-vector
    freshness check behind the unchanged-reply fast path of ReadTagBatch."""
    return blob_fingerprint(tags_blob(tags))


def abd_batch_signature(secret: bytes, tags, digest: str, nonce: int) -> bytes:
    """Intranet replica signature over a ReadTagBatch reply (tag vector +
    requested-keys digest + nonce) — the batched analogue of abd_signature."""
    return abd_batch_blob_signature(secret, tags_blob(tags), digest, nonce)


def abd_batch_blob_signature(
    secret: bytes, blob: bytes, digest: str, nonce: int
) -> bytes:
    """`abd_batch_signature` of the vector whose `tags_blob` this is, for
    a signer that keeps the blob."""
    return _mac(secret, blob + f"|{digest}|{nonce}".encode())


def validate_abd_batch_blob_signature(
    secret: bytes, blob: bytes, digest: str, nonce: int, given: bytes
) -> bool:
    """`validate_abd_batch_signature` for a verifier that wants the blob
    it formatted for more than the MAC (its fingerprint)."""
    return hmac.compare_digest(
        abd_batch_blob_signature(secret, blob, digest, nonce), given)


def validate_abd_batch_signature(
    secret: bytes, tags, digest: str, nonce: int, given: bytes
) -> bool:
    return validate_abd_batch_blob_signature(
        secret, tags_blob(tags), digest, nonce, given)


def abd_batch_unchanged_signature(
    secret: bytes, fingerprint: bytes, digest: str, nonce: int
) -> bytes:
    """Replica signature over an 'unchanged' ReadTagBatch reply: asserts
    "my tag vector for these keys fingerprints to `fingerprint`" without
    shipping (or re-serializing) the vector."""
    content = b"unchanged|" + fingerprint + f"|{digest}|{nonce}".encode()
    return _mac(secret, content)


def validate_abd_batch_unchanged_signature(
    secret: bytes, fingerprint: bytes, digest: str, nonce: int, given: bytes
) -> bool:
    return hmac.compare_digest(
        abd_batch_unchanged_signature(secret, fingerprint, digest, nonce), given
    )


def abd_batch_delta_signature(
    secret: bytes, base: bytes, fingerprint: bytes, positions, fields,
    digest: str, nonce: int,
) -> bytes:
    """Replica signature over a 'delta' ReadTagBatch reply: "since my
    vector fingerprinted to `base`, these positions were replaced and hold
    these tags (`fields`: their `tag_field`s), and the vector now
    fingerprints to `fingerprint`". Domain-separated from the full and the
    `unchanged` reply. Injective: the fingerprints go in as hex, a
    position is an integer, and a `tag_field` length-prefixes its id."""
    pairs = ";".join(f"{p}:{f}" for p, f in zip(positions, fields))
    return _mac(secret, (f"delta|{base.hex()}|{fingerprint.hex()}|{pairs}"
                         f"|{digest}|{nonce}").encode())


def validate_abd_batch_delta_signature(
    secret: bytes, base: bytes, fingerprint: bytes, positions, tags,
    digest: str, nonce: int, given: bytes,
) -> bool:
    """Verify a delta reply from the verifier's own tag objects: one
    `tag_field` per tag carried, none per key of the set."""
    return hmac.compare_digest(
        abd_batch_delta_signature(
            secret, base, fingerprint, positions,
            [tag_field(t) for t in tags], digest, nonce),
        given,
    )


def abd_keyset_unknown_signature(secret: bytes, digest: str, nonce: int) -> bytes:
    """Replica signature over a `KeySetUnknown` answer to a named
    ReadTagBatch: "I hold no key set under `digest`". Domain-separated
    from every reply that is a vote."""
    return _mac(secret, f"keyset-unknown|{digest}|{nonce}".encode())


def validate_abd_keyset_unknown_signature(
    secret: bytes, digest: str, nonce: int, given: bytes
) -> bool:
    return hmac.compare_digest(
        abd_keyset_unknown_signature(secret, digest, nonce), given)


def value_digest(value) -> str:
    """sha256 hex of a stored set's canonical form — the per-entry content
    commitment behind verified state transfer and Merkle anti-entropy. A
    manifest can attest a repository without shipping values; a seeded
    value is accepted only if it hashes back to the attested digest."""
    return hashlib.sha256(canonical(value).encode()).hexdigest()


def manifest_signature(secret: bytes, signer: str, manifest: dict, nonce: int) -> bytes:
    """Replica signature over its (key -> [seq, id, value-digest]) state
    manifest. Binds the SIGNER address so a relay (the supervisor forwards
    collected manifests to the recovering node) cannot re-attribute one
    replica's manifest to another when distinct signers are counted."""
    content = f"state-digest|{signer}|{canonical(manifest)}|{nonce}".encode()
    return _mac(secret, content)


def validate_manifest_signature(
    secret: bytes, signer: str, manifest: dict, nonce: int, given: bytes
) -> bool:
    return hmac.compare_digest(
        manifest_signature(secret, signer, manifest, nonce), given
    )


def antientropy_signature(secret: bytes, kind: str, payload, nonce: int) -> bytes:
    """Intranet signature over one anti-entropy reply (root / bucket vector /
    key listing). `kind` namespaces the phase so a captured reply of one
    phase cannot be replayed as another's."""
    content = f"ae-{kind}|{canonical(payload)}|{nonce}".encode()
    return _mac(secret, content)


def validate_antientropy_signature(
    secret: bytes, kind: str, payload, nonce: int, given: bytes
) -> bool:
    return hmac.compare_digest(
        antientropy_signature(secret, kind, payload, nonce), given
    )


_NO_VALUE = object()


def proxy_signature(secret: bytes, key: str, nonce: int, value=_NO_VALUE) -> bytes:
    """Proxy<->replica signature; two arities like `Utils.scala:42-49`."""
    if value is _NO_VALUE:
        content = f"{key}|{nonce}".encode()
    else:
        content = f"{key}|{canonical(value)}|{nonce}".encode()
    return _mac(secret, content)


def validate_proxy_signature(secret: bytes, key: str, nonce: int, given: bytes, value=_NO_VALUE) -> bool:
    if value is _NO_VALUE:
        return hmac.compare_digest(proxy_signature(secret, key, nonce), given)
    return hmac.compare_digest(proxy_signature(secret, key, nonce, value), given)
