// Host-side index accumulator: the C++ hot loop of indexing.
//
// Plays the role the JVM+Lucene IndexWriter RAM buffer plays in the
// reference (reference behavior: index/engine/InternalEngine.java:1387
// feeding IndexWriter.addDocuments; the native-component inventory is
// SURVEY.md §2.2). Everything per-token — tokenization, term hashing,
// postings/position accumulation — happens here; Python/numpy handles the
// per-term vectorized packing into blocked-CSR arrays.
//
// Contract (kept bit-compatible with the pure-Python PackBuilder):
//   - ASCII fast-path tokenizer == analysis/analyzers.py StandardAnalyzer
//     for ASCII input: runs of [A-Za-z0-9] with one optional interior
//     apostrophe group, lowercased, 255-char split, stopword-free.
//   - positions keys: docid * POS_L + pos, dropped at pos >= POS_L - 64,
//     multi-value gap handled by the caller via pos_base.
//   - term sort order: (field sort rank, term bytes) — UTF-8 byte order ==
//     code-point order, matching Python's sorted(postings.keys()).
//
// Exposed as a C ABI for ctypes; all buffers are caller-allocated numpy.

#include <cstdint>
#include <cstring>
#include <string>
#include <unordered_map>
#include <vector>
#include <algorithm>

namespace {

constexpr int64_t POS_L = 1 << 17;
constexpr int MAX_TOKEN_LEN = 255;

struct TermEntry {
    std::vector<int32_t> docs;
    std::vector<float> tfs;
    std::vector<int64_t> pos_keys;
    void add(int32_t doc, float tf_inc) {
        if (!docs.empty() && docs.back() == doc) {
            tfs.back() += tf_inc;
        } else {
            docs.push_back(doc);
            tfs.push_back(tf_inc);
        }
    }
};

struct FieldLen {
    int32_t doc;
    int32_t len;
};

struct Builder {
    // key = field_id (4 bytes big-endian) + term bytes
    std::unordered_map<std::string, TermEntry> terms;
    std::vector<std::vector<FieldLen>> field_lens;  // per field_id
    std::string keybuf;

    TermEntry& entry(uint32_t field_id, const char* term, size_t len) {
        keybuf.resize(4 + len);
        keybuf[0] = (char)(field_id >> 24);
        keybuf[1] = (char)(field_id >> 16);
        keybuf[2] = (char)(field_id >> 8);
        keybuf[3] = (char)(field_id);
        memcpy(&keybuf[4], term, len);
        return terms[keybuf];
    }
};

inline bool is_word(unsigned char c) {
    return (c >= 'a' && c <= 'z') || (c >= 'A' && c <= 'Z') ||
           (c >= '0' && c <= '9');
}

}  // namespace

extern "C" {

void* builder_new() { return new Builder(); }

void builder_free(void* h) { delete static_cast<Builder*>(h); }

// Tokenize ASCII text with standard-analyzer semantics and accumulate
// postings + positions for (field_id, docid). pos_base offsets positions
// (multi-value gap handled by caller). record_positions == 0 skips position
// keys (fields with index_options that exclude positions).
// Returns (last_position + 1) or 0 if no tokens; -1 if non-ASCII byte seen
// (caller must fall back to Python tokenization for this value).
int64_t builder_add_text(void* h, uint32_t field_id, int32_t docid,
                         const char* text, int64_t len, int64_t pos_base,
                         int record_positions) {
    for (int64_t i = 0; i < len; i++) {
        if ((unsigned char)text[i] >= 0x80) return -1;
    }
    Builder* b = static_cast<Builder*>(h);
    char lower[MAX_TOKEN_LEN];
    int64_t pos = 0;
    int64_t i = 0;
    int64_t n_tokens = 0;
    while (i < len) {
        if (!is_word((unsigned char)text[i])) { i++; continue; }
        int64_t start = i;
        while (i < len && is_word((unsigned char)text[i])) i++;
        // one optional interior apostrophe group: 'x+ (ASCII quote only;
        // the Python regex also accepts U+2019 but that is non-ASCII input)
        if (i < len && text[i] == '\'' && i + 1 < len &&
            is_word((unsigned char)text[i + 1])) {
            i++;
            while (i < len && is_word((unsigned char)text[i])) i++;
        }
        int64_t tlen = i - start;
        // overlong tokens split at MAX_TOKEN_LEN boundaries (each piece is
        // its own token+position, matching Analyzer.analyze)
        for (int64_t off = 0; off < tlen; off += MAX_TOKEN_LEN) {
            int64_t plen = std::min<int64_t>(MAX_TOKEN_LEN, tlen - off);
            for (int64_t j2 = 0; j2 < plen; j2++) {
                char c = text[start + off + j2];
                lower[j2] = (c >= 'A' && c <= 'Z') ? c + 32 : c;
            }
            TermEntry& e = b->entry(field_id, lower, plen);
            e.add(docid, 1.0f);
            int64_t p = pos_base + pos;
            if (record_positions && p < POS_L - 64) {
                e.pos_keys.push_back((int64_t)docid * POS_L + p);
            }
            pos++;
            n_tokens++;
        }
    }
    (void)n_tokens;
    return pos;
}

// Document -> shard for n ASCII ids (cluster/routing.py shard_for_id, one
// call for all of them): MurmurHash3 x86 32-bit, seed 0, over the id's
// UTF-16 code units little-endian, as the reference's Murmur3HashFunction
// hashes a String (an ASCII char c is the two bytes c, 0), then
// floorMod(hash, routing_num_shards) / routing_factor. id k is
// ids[off[k], off[k+1]).
void route_ascii_ids(const char* ids, const int64_t* off, int64_t n,
                     int32_t routing_num_shards, int32_t routing_factor,
                     int32_t* out) {
    const uint32_t c1 = 0xCC9E2D51u, c2 = 0x1B873593u;
    auto rotl = [](uint32_t x, int r) { return (x << r) | (x >> (32 - r)); };
    for (int64_t d = 0; d < n; d++) {
        const unsigned char* s = (const unsigned char*)ids + off[d];
        const int64_t chars = off[d + 1] - off[d];
        uint32_t h = 0;
        int64_t i = 0;
        for (; i + 1 < chars; i += 2) {          // four bytes: two chars
            uint32_t k = (uint32_t)s[i] | ((uint32_t)s[i + 1] << 16);
            k *= c1; k = rotl(k, 15); k *= c2;
            h ^= k; h = rotl(h, 13); h = h * 5 + 0xE6546B64u;
        }
        if (i < chars) {                         // a tail of two bytes: c, 0
            uint32_t k = (uint32_t)s[i];
            k *= c1; k = rotl(k, 15); k *= c2;
            h ^= k;
        }
        h ^= (uint32_t)(2 * chars);
        h ^= h >> 16; h *= 0x85EBCA6Bu; h ^= h >> 13; h *= 0xC2B2AE35u;
        h ^= h >> 16;
        int64_t m = (int64_t)(int32_t)h % routing_num_shards;
        if (m < 0) m += routing_num_shards;
        out[d] = (int32_t)(m / routing_factor);
    }
}

// A whole burst through builder_add_text in ONE call, so that the caller
// (one Python thread a shard, ctypes releasing the interpreter lock for the
// call) holds that lock once a burst and not once a value: n values, value
// k being text[off[k], off[k+1]) of document docids[k], a document's
// values adjacent. Positions chain across one document's values with the
// +100 gap, exactly as PackBuilder._add_text_native chains them value by
// value; counts[k] receives value k's token count. All or nothing: -1, and
// nothing added, if any byte is not ASCII (the caller then goes value by
// value); else 0.
int64_t builder_add_texts(void* h, uint32_t field_id, const int32_t* docids,
                          const char* text, const int64_t* off, int64_t n,
                          int record_positions, int64_t* counts) {
    const int64_t total = n ? off[n] : 0;
    for (int64_t i = 0; i < total; i++) {
        if ((unsigned char)text[i] >= 0x80) return -1;
    }
    int64_t pos_base = 0;
    for (int64_t k = 0; k < n; k++) {
        if (k == 0 || docids[k] != docids[k - 1]) pos_base = 0;
        int64_t ret = builder_add_text(h, field_id, docids[k], text + off[k],
                                       off[k + 1] - off[k], pos_base,
                                       record_positions);
        counts[k] = ret;
        pos_base += ret + 100;
    }
    return 0;
}

// Pre-tokenized path (Python analyzer fallback / keyword terms).
// terms = concatenated UTF-8 bytes; lens[i] each term's length;
// positions[i] absolute position or -1 (skip position key); tf_inc added
// per token (keywords pass 1.0 repeatedly to accumulate multi-value tf).
void builder_add_tokens(void* h, uint32_t field_id, int32_t docid,
                        const char* terms, const int32_t* lens,
                        const int64_t* positions, int64_t n) {
    Builder* b = static_cast<Builder*>(h);
    const char* p = terms;
    for (int64_t i = 0; i < n; i++) {
        TermEntry& e = b->entry(field_id, p, lens[i]);
        e.add(docid, 1.0f);
        if (positions[i] >= 0 && positions[i] < POS_L - 64) {
            e.pos_keys.push_back((int64_t)docid * POS_L + positions[i]);
        }
        p += lens[i];
    }
}

// Record one text value's token count toward the field's doc length/norms.
void builder_add_field_len(void* h, uint32_t field_id, int32_t docid,
                           int32_t len) {
    Builder* b = static_cast<Builder*>(h);
    if (b->field_lens.size() <= field_id) b->field_lens.resize(field_id + 1);
    auto& v = b->field_lens[field_id];
    if (!v.empty() && v.back().doc == docid) {
        v.back().len += len;
    } else {
        v.push_back({docid, len});
    }
}

// ---- pack phase ----------------------------------------------------------

struct PackSizes {
    int64_t n_terms;
    int64_t term_bytes;
    int64_t n_postings;
    int64_t n_positions;
};

// Sort terms by (field_rank, term bytes) and report output sizes.
// field_rank[field_id] is the rank of the field name in Python's sort order.
// The sorted order is cached on the builder for the fill call.
struct SortedRef {
    uint32_t rank;
    const std::string* key;
    const TermEntry* entry;
};

static thread_local std::vector<SortedRef> g_sorted;

void builder_pack_sizes(void* h, const uint32_t* field_rank,
                        int64_t n_fields, PackSizes* out) {
    Builder* b = static_cast<Builder*>(h);
    g_sorted.clear();
    g_sorted.reserve(b->terms.size());
    int64_t tb = 0, np = 0, npos = 0;
    for (auto& kv : b->terms) {
        uint32_t fid = ((uint32_t)(unsigned char)kv.first[0] << 24) |
                       ((uint32_t)(unsigned char)kv.first[1] << 16) |
                       ((uint32_t)(unsigned char)kv.first[2] << 8) |
                       (uint32_t)(unsigned char)kv.first[3];
        uint32_t rank = fid < (uint32_t)n_fields ? field_rank[fid] : fid;
        g_sorted.push_back({rank, &kv.first, &kv.second});
        tb += (int64_t)kv.first.size() - 4;
        np += (int64_t)kv.second.docs.size();
        npos += (int64_t)kv.second.pos_keys.size();
    }
    std::sort(g_sorted.begin(), g_sorted.end(),
              [](const SortedRef& a, const SortedRef& c) {
                  if (a.rank != c.rank) return a.rank < c.rank;
                  // unsigned byte order: UTF-8 byte order == code-point
                  // order, matching Python's str sort (char is signed!)
                  const unsigned char* ab =
                      (const unsigned char*)a.key->data() + 4;
                  const unsigned char* cb =
                      (const unsigned char*)c.key->data() + 4;
                  return std::lexicographical_compare(
                      ab, ab + a.key->size() - 4, cb, cb + c.key->size() - 4);
              });
    out->n_terms = (int64_t)g_sorted.size();
    out->term_bytes = tb;
    out->n_postings = np;
    out->n_positions = npos;
}

// Fill caller-allocated buffers in the order computed by builder_pack_sizes.
void builder_pack_fill(void* h, char* term_buf, int32_t* term_lens,
                       uint32_t* term_fids, int64_t* post_offsets,
                       int32_t* flat_docs, float* flat_tfs,
                       int64_t* pos_offsets, int64_t* flat_pos) {
    (void)h;
    int64_t tb = 0, np = 0, npos = 0;
    int64_t t = 0;
    post_offsets[0] = 0;
    pos_offsets[0] = 0;
    for (const auto& ref : g_sorted) {
        const std::string& key = *ref.key;
        const TermEntry& e = *ref.entry;
        int64_t tl = (int64_t)key.size() - 4;
        memcpy(term_buf + tb, key.data() + 4, tl);
        tb += tl;
        term_lens[t] = (int32_t)tl;
        term_fids[t] = ((uint32_t)(unsigned char)key[0] << 24) |
                       ((uint32_t)(unsigned char)key[1] << 16) |
                       ((uint32_t)(unsigned char)key[2] << 8) |
                       (uint32_t)(unsigned char)key[3];
        memcpy(flat_docs + np, e.docs.data(), e.docs.size() * sizeof(int32_t));
        memcpy(flat_tfs + np, e.tfs.data(), e.tfs.size() * sizeof(float));
        np += (int64_t)e.docs.size();
        memcpy(flat_pos + npos, e.pos_keys.data(),
               e.pos_keys.size() * sizeof(int64_t));
        npos += (int64_t)e.pos_keys.size();
        t++;
        post_offsets[t] = np;
        pos_offsets[t] = npos;
    }
    g_sorted.clear();
    g_sorted.shrink_to_fit();
}

// Per-field doc-length export: sizes then fill.
int64_t builder_field_len_count(void* h, uint32_t field_id) {
    Builder* b = static_cast<Builder*>(h);
    if (b->field_lens.size() <= field_id) return 0;
    return (int64_t)b->field_lens[field_id].size();
}

void builder_field_len_fill(void* h, uint32_t field_id, int32_t* docs,
                            int32_t* lens) {
    Builder* b = static_cast<Builder*>(h);
    auto& v = b->field_lens[field_id];
    for (size_t i = 0; i < v.size(); i++) {
        docs[i] = v[i].doc;
        lens[i] = v[i].len;
    }
}

}  // extern "C"
